"""JWKS + third-party JWT verification (reference core/src/iam/jwks.rs +
iam/verify.rs): RS256 tokens verified against a JWKS endpoint selected by
kid, HS256 against a configured key; caching and capability gating."""

import base64
import hashlib
import hmac
import json
import secrets
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from surrealdb_tpu import Datastore
from surrealdb_tpu.err import SdbError
from surrealdb_tpu.iam import authenticate
from surrealdb_tpu.kvs.ds import Session


def _b64(b: bytes) -> str:
    return base64.urlsafe_b64encode(b).decode().rstrip("=")


def _miller_rabin(n, rounds=24):
    if n % 2 == 0:
        return n == 2
    d, r = n - 1, 0
    while d % 2 == 0:
        d //= 2
        r += 1
    for _ in range(rounds):
        a = secrets.randbelow(n - 3) + 2
        x = pow(a, d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = pow(x, 2, n)
            if x == n - 1:
                break
        else:
            return False
    return True


def _prime(bits):
    while True:
        p = secrets.randbits(bits) | (1 << (bits - 1)) | 1
        if _miller_rabin(p):
            return p


def _rsa_keypair(bits=768):
    e = 65537
    while True:
        p, q = _prime(bits // 2), _prime(bits // 2)
        n = p * q
        phi = (p - 1) * (q - 1)
        if phi % e:
            d = pow(e, -1, phi)
            return n, e, d


def _rs256_sign(n, d, header: dict, payload: dict) -> str:
    h = _b64(json.dumps(header).encode())
    p = _b64(json.dumps(payload).encode())
    msg = f"{h}.{p}".encode()
    k = (n.bit_length() + 7) // 8
    di = bytes.fromhex("3031300d060960864801650304020105000420")
    t = di + hashlib.sha256(msg).digest()
    em = b"\x00\x01" + b"\xff" * (k - len(t) - 3) + b"\x00" + t
    sig = pow(int.from_bytes(em, "big"), d, n).to_bytes(k, "big")
    return f"{h}.{p}.{_b64(sig)}"


def _spawn_jwks(doc: dict):
    class H(BaseHTTPRequestHandler):
        hits = [0]

        def do_GET(self):
            H.hits[0] += 1
            body = json.dumps(doc).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = HTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, H, f"http://127.0.0.1:{srv.server_port}/jwks.json"


@pytest.fixture(scope="module")
def rsa():
    return _rsa_keypair()


def test_jwks_rs256_roundtrip(rsa):
    n, e, d = rsa
    jwks = {"keys": [
        {"kty": "RSA", "kid": "k1", "alg": "RS256",
         "n": _b64(n.to_bytes((n.bit_length() + 7) // 8, "big")),
         "e": _b64(e.to_bytes(3, "big"))},
    ]}
    srv, H, url = _spawn_jwks(jwks)
    try:
        ds = Datastore("memory")
        from surrealdb_tpu.capabilities import Capabilities, Targets

        ds.capabilities = Capabilities(allow_net=Targets.parse("127.0.0.1"))
        ds.query(f"DEFINE ACCESS ext ON DATABASE TYPE JWT URL '{url}'",
                 ns="t", db="t")
        ds.query("CREATE user:7", ns="t", db="t")
        tok = _rs256_sign(n, d, {"alg": "RS256", "kid": "k1"},
                          {"AC": "ext", "NS": "t", "DB": "t",
                           "ID": "user:7", "exp": time.time() + 3600})
        sess = Session()
        authenticate(ds, sess, tok)
        assert sess.auth_level == "record"
        assert str(sess.rid.id) == "7"
        # cached: a second authenticate doesn't refetch
        hits = H.hits[0]
        authenticate(ds, Session(), tok)
        assert H.hits[0] == hits
        # tampered payload fails
        h, p, s = tok.split(".")
        bad = f"{h}.{_b64(json.dumps({'AC': 'ext', 'NS': 't', 'DB': 't', 'ID': 'user:1'}).encode())}.{s}"
        with pytest.raises(SdbError):
            authenticate(ds, Session(), bad)
    finally:
        srv.shutdown()


def test_access_hs256_custom_key():
    ds = Datastore("memory")
    ds.query(
        "DEFINE ACCESS partner ON DATABASE TYPE JWT ALGORITHM HS256 "
        "KEY 'sharedsecret'", ns="t", db="t")
    h = _b64(json.dumps({"alg": "HS256"}).encode())
    p = _b64(json.dumps({"AC": "partner", "NS": "t", "DB": "t",
                         "ID": "user:9",
                         "exp": time.time() + 60}).encode())
    sig = hmac.new(b"sharedsecret", f"{h}.{p}".encode(),
                   hashlib.sha256).digest()
    tok = f"{h}.{p}.{_b64(sig)}"
    sess = Session()
    authenticate(ds, sess, tok)
    assert sess.auth_level == "record" and sess.ac == "partner"
    wrong = hmac.new(b"other", f"{h}.{p}".encode(), hashlib.sha256).digest()
    with pytest.raises(SdbError):
        authenticate(ds, Session(), f"{h}.{p}.{_b64(wrong)}")


def test_expired_external_token(rsa):
    n, e, d = rsa
    ds = Datastore("memory")
    ds.query(
        "DEFINE ACCESS old ON DATABASE TYPE JWT ALGORITHM HS256 KEY 'k'",
        ns="t", db="t")
    h = _b64(json.dumps({"alg": "HS256"}).encode())
    p = _b64(json.dumps({"AC": "old", "NS": "t", "DB": "t", "ID": "u:1",
                         "exp": time.time() - 10}).encode())
    sig = hmac.new(b"k", f"{h}.{p}".encode(), hashlib.sha256).digest()
    with pytest.raises(SdbError, match="expired"):
        authenticate(ds, Session(), f"{h}.{p}.{_b64(sig)}")


def test_alg_confusion_blocked(rsa):
    # ADVICE r5 (high): with ALGORITHM unset, the attacker-controlled
    # header alg must NOT be trusted — an HS token HMAC-signed with the
    # public PEM text as the secret must be rejected
    n, e, d = rsa
    import base64 as _b

    der_n = n.to_bytes((n.bit_length() + 7) // 8, "big")
    # minimal PKCS#1 public DER wrapped as PEM
    def _der_int(x):
        b = x.to_bytes((x.bit_length() + 7) // 8 or 1, "big")
        if b[0] & 0x80:
            b = b"\x00" + b
        return bytes([0x02, len(b)]) + b
    seq = _der_int(n) + _der_int(e)
    der = bytes([0x30, 0x82]) + len(seq).to_bytes(2, "big") + seq
    pem = ("-----BEGIN RSA PUBLIC KEY-----\n"
           + _b.encodebytes(der).decode()
           + "-----END RSA PUBLIC KEY-----\n")
    ds = Datastore("memory")
    ds.query(
        f"DEFINE ACCESS ext ON DATABASE TYPE JWT KEY '{pem}'",
        ns="t", db="t")
    for alg, hn in (("HS256", hashlib.sha256), ("HS384", hashlib.sha384)):
        h = _b64(json.dumps({"alg": alg}).encode())
        p = _b64(json.dumps({"AC": "ext", "NS": "t", "DB": "t",
                             "ID": "user:1",
                             "exp": time.time() + 60}).encode())
        sig = hmac.new(pem.encode(), f"{h}.{p}".encode(), hn).digest()
        with pytest.raises(SdbError):
            authenticate(ds, Session(), f"{h}.{p}.{_b64(sig)}")
    # the config pins HS512 by default (reference default) — a legit
    # HS512 token with the configured key text still verifies
    h = _b64(json.dumps({"alg": "HS512"}).encode())
    p = _b64(json.dumps({"AC": "ext", "NS": "t", "DB": "t", "ID": "user:2",
                         "exp": time.time() + 60}).encode())
    sig = hmac.new(pem.encode(), f"{h}.{p}".encode(), hashlib.sha512).digest()
    sess = Session()
    authenticate(ds, sess, f"{h}.{p}.{_b64(sig)}")
    assert sess.auth_level == "record"


@pytest.mark.parametrize("target", ["user", "user:2e58abcdefghijklmnop"])
def test_record_access_with_jwt_roundtrips(target):
    # ADVICE r5 (medium): signup tokens for a record access WITH JWT must
    # be verifiable by authenticate (signed with the configured key).
    # The token's ID claim is the rendered record id and authenticate
    # parses it back: a generated key that opens like a float (about one
    # in 300 does) has to survive that, so one case pins such a key.
    from surrealdb_tpu.iam import signup

    ds = Datastore("memory")
    ds.query(
        "DEFINE ACCESS acc ON DATABASE TYPE RECORD "
        f"SIGNUP (CREATE {target} SET email = $email) "
        "SIGNIN (SELECT * FROM user WHERE email = $email) "
        "WITH JWT ALGORITHM HS256 KEY 'issuerkey'",
        ns="t", db="t")
    tok = signup(ds, Session(), {"NS": "t", "DB": "t", "AC": "acc",
                                 "email": "a"})
    # token is signed with the configured key, not the datastore secret
    h, p, s = tok.split(".")
    assert json.loads(base64.urlsafe_b64decode(h + "==")).get("alg") == "HS256"
    want = hmac.new(b"issuerkey", f"{h}.{p}".encode(), hashlib.sha256).digest()
    assert hmac.compare_digest(want, base64.urlsafe_b64decode(s + "=="))
    sess = Session()
    authenticate(ds, sess, tok)
    assert sess.auth_level == "record" and sess.ac == "acc"
    assert sess.rid.tb == "user"
    if ":" in target:
        assert sess.rid.id == target.split(":")[1]


def test_external_token_requires_exp_and_honours_nbf():
    ds = Datastore("memory")
    ds.query(
        "DEFINE ACCESS p ON DATABASE TYPE JWT ALGORITHM HS256 KEY 'k'",
        ns="t", db="t")

    def tok(payload):
        h = _b64(json.dumps({"alg": "HS256"}).encode())
        p = _b64(json.dumps(payload).encode())
        sig = hmac.new(b"k", f"{h}.{p}".encode(), hashlib.sha256).digest()
        return f"{h}.{p}.{_b64(sig)}"

    base = {"AC": "p", "NS": "t", "DB": "t", "ID": "u:1"}
    with pytest.raises(SdbError):  # no exp at all
        authenticate(ds, Session(), tok(base))
    with pytest.raises(SdbError):  # not valid yet
        authenticate(ds, Session(),
                     tok({**base, "exp": time.time() + 60,
                          "nbf": time.time() + 30}))
    authenticate(ds, Session(),
                 tok({**base, "exp": time.time() + 60,
                      "nbf": time.time() - 30}))


def test_authenticate_clause_runs():
    ds = Datastore("memory")
    ds.query(
        "DEFINE ACCESS g ON DATABASE TYPE JWT ALGORITHM HS256 KEY 'k' "
        "AUTHENTICATE { IF $token.deny { THROW 'denied' } }",
        ns="t", db="t")

    def tok(payload):
        h = _b64(json.dumps({"alg": "HS256"}).encode())
        p = _b64(json.dumps(payload).encode())
        sig = hmac.new(b"k", f"{h}.{p}".encode(), hashlib.sha256).digest()
        return f"{h}.{p}.{_b64(sig)}"

    base = {"AC": "g", "NS": "t", "DB": "t", "ID": "u:1",
            "exp": time.time() + 60}
    authenticate(ds, Session(), tok(base))
    with pytest.raises(SdbError, match="denied"):
        authenticate(ds, Session(), tok({**base, "deny": True}))
