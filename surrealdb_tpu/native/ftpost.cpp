// Full-text posting values read without the interpreter.
//
// A FULLTEXT index keeps one KV value a term (idx/fulltext.py `bf` keys):
// a pickled dict {record key bytes: (tf, [offsets...], record id)}, the
// store's pickle framing byte 0x00 first. `sdb_ft_postings` walks the
// pickle opcodes of many such values and writes, for every posting, the
// record id and the tf; the offsets are skipped, never built. It knows the
// opcodes `pickle.dumps` writes for that shape (protocols 2 to 5, memo
// included) and nothing else: a value with any other opcode, or whose
// postings are not (int, list, int) triples under bytes keys, makes the
// call answer -1 - (that value's index), and the caller decodes in Python.

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

enum Kind : uint8_t { MARK, INT, BYTES, LIST, TUPLE, POSTING, DICT, OTHER };

struct Item {
  Kind kind;
  int64_t a;  // INT: the value; POSTING: tf
  int64_t b;  // POSTING: the record id
};

struct Reader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;

  bool need(size_t n) {
    if (static_cast<size_t>(end - p) < n) ok = false;
    return ok;
  }
  uint64_t le(size_t n) {
    if (!need(n)) return 0;
    uint64_t v = 0;
    for (size_t i = 0; i < n; ++i) v |= static_cast<uint64_t>(p[i]) << (8 * i);
    p += n;
    return v;
  }
};

// One value's postings appended to (rids, tfs); false where the value is
// not the shape.
bool one_value(const uint8_t* data, int64_t len, std::vector<Item>& st,
               std::vector<Item>& memo, int64_t* rids, int32_t* tfs,
               int64_t cap, int64_t* n_out) {
  if (len < 1 || data[0] != 0x00) return false;
  Reader r{data + 1, data + len};
  st.clear();
  memo.clear();
  auto pop_mark = [&](size_t& at) -> bool {
    for (size_t i = st.size(); i-- > 0;) {
      if (st[i].kind == MARK) {
        at = i;
        return true;
      }
    }
    return false;
  };
  auto tuple = [&](size_t from) {
    size_t n = st.size() - from;
    Item t{TUPLE, 0, 0};
    if (n == 3 && st[from].kind == INT && st[from + 1].kind == LIST &&
        st[from + 2].kind == INT)
      t = Item{POSTING, st[from].a, st[from + 2].a};
    st.resize(from);
    st.push_back(t);
  };
  auto emit = [&](const Item& k, const Item& v) -> bool {
    if (k.kind != BYTES || v.kind != POSTING || *n_out >= cap) return false;
    if (v.a < INT32_MIN || v.a > INT32_MAX) return false;
    rids[*n_out] = v.b;
    tfs[*n_out] = static_cast<int32_t>(v.a);
    ++*n_out;
    return true;
  };
  while (r.ok && r.p < r.end) {
    uint8_t op = *r.p++;
    switch (op) {
      case 0x80:  // PROTO
        r.le(1);
        break;
      case 0x95:  // FRAME
        r.le(8);
        break;
      case '}':
        st.push_back({DICT, 0, 0});
        break;
      case ']':
        st.push_back({LIST, 0, 0});
        break;
      case ')':
        st.push_back({TUPLE, 0, 0});
        break;
      case '(':
        st.push_back({MARK, 0, 0});
        break;
      case 'N':
      case 0x88:
      case 0x89:
        st.push_back({OTHER, 0, 0});
        break;
      case 'K':
        st.push_back({INT, static_cast<int64_t>(r.le(1)), 0});
        break;
      case 'M':
        st.push_back({INT, static_cast<int64_t>(r.le(2)), 0});
        break;
      case 'J':
        st.push_back({INT, static_cast<int64_t>(
                               static_cast<int32_t>(r.le(4))), 0});
        break;
      case 0x8a: {  // LONG1: little-endian two's complement
        uint64_t n = r.le(1);
        if (n > 8 || !r.need(n)) return false;
        uint64_t v = r.le(n);
        if (n > 0 && n < 8 && (v >> (8 * n - 1)) & 1) v |= ~0ull << (8 * n);
        st.push_back({INT, static_cast<int64_t>(v), 0});
        break;
      }
      case 'C': {
        uint64_t n = r.le(1);
        if (!r.need(n)) return false;
        r.p += n;
        st.push_back({BYTES, 0, 0});
        break;
      }
      case 'B':
      case 0x8e: {
        uint64_t n = r.le(op == 'B' ? 4 : 8);
        if (!r.need(n)) return false;
        r.p += n;
        st.push_back({BYTES, 0, 0});
        break;
      }
      case 0x94:  // MEMOIZE
        if (st.empty()) return false;
        memo.push_back(st.back());
        break;
      case 'q':
      case 'r': {
        uint64_t i = r.le(op == 'q' ? 1 : 4);
        // pickle.dumps numbers its memo entries in order from 0
        if (st.empty() || i > memo.size()) return false;
        if (memo.size() == i) memo.push_back(Item{OTHER, 0, 0});
        memo[i] = st.back();
        break;
      }
      case 'h':
      case 'j': {
        uint64_t i = r.le(op == 'h' ? 1 : 4);
        if (i >= memo.size()) return false;
        st.push_back(memo[i]);
        break;
      }
      case 'a':
        if (st.size() < 2 || st[st.size() - 2].kind != LIST) return false;
        st.pop_back();
        break;
      case 'e': {
        size_t at;
        if (!pop_mark(at) || at == 0 || st[at - 1].kind != LIST) return false;
        st.resize(at);
        break;
      }
      case 't': {
        size_t at;
        if (!pop_mark(at)) return false;
        st.erase(st.begin() + at);
        tuple(at);
        break;
      }
      case 0x85:
      case 0x86:
      case 0x87: {
        size_t n = op - 0x84;
        if (st.size() < n) return false;
        tuple(st.size() - n);
        break;
      }
      case 's': {
        if (st.size() < 3 || st[st.size() - 3].kind != DICT) return false;
        if (!emit(st[st.size() - 2], st.back())) return false;
        st.resize(st.size() - 2);
        break;
      }
      case 'u': {
        size_t at;
        if (!pop_mark(at) || at == 0 || st[at - 1].kind != DICT) return false;
        if ((st.size() - at - 1) % 2) return false;
        for (size_t i = at + 1; i < st.size(); i += 2)
          if (!emit(st[i], st[i + 1])) return false;
        st.resize(at);
        break;
      }
      case '.':
        return r.p == r.end && st.size() == 1 && st[0].kind == DICT;
      default:
        return false;
    }
  }
  return false;
}

}  // namespace

extern "C" {

// `n` values laid end to end in `data`, value i at [offs[i], offs[i+1]).
// Writes counts[i] (postings of value i) and every posting's record id and
// tf, value after value, at most `cap`. Returns the postings written, or
// -1 - i where value i is not the shape.
int64_t sdb_ft_postings(const uint8_t* data, const int64_t* offs, int64_t n,
                        int64_t* counts, int64_t* rids, int32_t* tfs,
                        int64_t cap) {
  std::vector<Item> st, memo;
  st.reserve(64);
  int64_t total = 0;
  for (int64_t i = 0; i < n; ++i) {
    int64_t before = total;
    if (!one_value(data + offs[i], offs[i + 1] - offs[i], st, memo, rids,
                   tfs, cap, &total))
      return -1 - i;
    counts[i] = total - before;
  }
  return total;
}
}
