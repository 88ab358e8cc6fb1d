// Float arrays of a JSON text, parsed natively.
//
// One pass over the raw bytes of a JSON document finds every innermost
// array, outside any string, whose elements are all JSON numbers with a
// fraction or an exponent (what Python's json module turns into floats),
// at least `min_count` of them, each read completely by std::from_chars
// without a range error. Each such array is written to `spans` as
// (byte offset of '[', byte offset one past ']', element count) and its
// values, in order, to `vals` as doubles. std::from_chars rounds
// correctly, as float() does, so the doubles are the ones json.loads
// would give.
//
// An array that holds anything else (an integer literal, a string, an
// object, a nested array, NaN or Infinity, a number out of range) is not
// taken; the caller's json.loads decodes it. The scan never fails on
// malformed JSON: it only ever takes arrays that are valid on their own,
// and json.loads judges the rest.

#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <system_error>

namespace {

inline bool is_ws(char c) {
    return c == ' ' || c == '\t' || c == '\n' || c == '\r';
}

inline bool is_digit(char c) { return c >= '0' && c <= '9'; }

// Reads the JSON number that starts at `p` into `*v` when it has a
// fraction or an exponent (RFC 8259: -?(0|[1-9][0-9]*)(.[0-9]+)?
// ([eE][+-]?[0-9]+)?), and returns its end; nullptr when the text there
// is an integer literal or no JSON number. The integer part and the
// character after it are checked here; std::from_chars reads the rest,
// which it takes exactly as JSON does, and stops where JSON would.
// `*in_range` is false when the value overflowed, underflowed or is not
// finite.
const char* read_float(const char* p, const char* end, double* v,
                       bool* in_range) {
    const char* s = p + (p < end && *p == '-');
    if (s >= end || !is_digit(*s)) return nullptr;
    const char* t = s + 1;
    if (*s != '0') {
        while (t < end && is_digit(*t)) ++t;
    }
    if (t >= end) return nullptr;
    if (*t == '.') {
        if (t + 1 >= end || !is_digit(t[1])) return nullptr;
    } else if (*t != 'e' && *t != 'E') {
        return nullptr;  // an integer literal, or a leading zero
    }
    auto r = std::from_chars(p, end, *v);
    if (r.ec == std::errc::invalid_argument) return nullptr;
    *in_range = r.ec == std::errc() && std::isfinite(*v);
    return r.ptr;
}

}  // namespace

extern "C" {

// Returns the number of arrays taken; -1 when `spans` or `vals` ran out
// of room; -2 when a string holds the escape `\u0000`, which the caller's
// placeholders begin with. `*refused` is the number of arrays whose
// elements were all JSON float literals, at least `min_count` of them,
// but that std::from_chars did not read completely and in range.
int64_t sdb_json_float_arrays(const char* buf, int64_t n, int64_t min_count,
                              int64_t* spans, int64_t max_spans,
                              double* vals, int64_t max_vals,
                              int64_t* refused) {
    const char* const base = buf;
    const char* const end = buf + n;
    int64_t nspans = 0;
    int64_t nvals = 0;
    *refused = 0;
    const char* p = buf;
    while (p < end) {
        char c = *p;
        if (c == '"') {
            // a string: skip to its closing quote, stepping over escapes
            ++p;
            while (p < end && *p != '"') {
                if (*p != '\\') {
                    ++p;
                    continue;
                }
                if (end - p >= 6 && std::memcmp(p + 1, "u0000", 5) == 0) {
                    return -2;
                }
                p += (end - p >= 2) ? 2 : 1;
            }
            if (p < end) ++p;  // the closing quote
            continue;
        }
        if (c != '[') {
            ++p;
            continue;
        }
        // an array opens: read it as a float array until it proves not
        // to be one; on the first surprise the scan resumes right after
        // the '[' so that arrays nested in it are still seen
        const char* open = p;
        const char* q = p + 1;
        int64_t count = 0;
        bool ok = true;
        bool all_read = true;
        const int64_t first = nvals;
        while (q < end && is_ws(*q)) ++q;
        if (q < end && *q == ']') ok = false;  // empty
        while (ok) {
            if (nvals >= max_vals) return -1;
            bool in_range = true;
            const char* num_end = read_float(q, end, &vals[nvals], &in_range);
            if (num_end == nullptr) {
                ok = false;
                break;
            }
            all_read = all_read && in_range;
            ++nvals;
            ++count;
            q = num_end;
            while (q < end && is_ws(*q)) ++q;
            if (q < end && *q == ',') {
                ++q;
                while (q < end && is_ws(*q)) ++q;
                continue;
            }
            if (q < end && *q == ']') break;
            ok = false;
        }
        if (ok && count >= min_count) {
            if (all_read) {
                if (nspans >= max_spans) return -1;
                spans[3 * nspans] = open - base;
                spans[3 * nspans + 1] = (q + 1) - base;
                spans[3 * nspans + 2] = count;
                ++nspans;
            } else {
                ++*refused;
                nvals = first;
            }
            p = q + 1;
            continue;
        }
        nvals = first;
        p = open + 1;
    }
    return nspans;
}

}  // extern "C"
