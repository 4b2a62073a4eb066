// Batched modular exponentiation at any width, through OpenSSL's BN.
//
// The host twin of ops/modmath.py's wide limb families: a 2048-bit
// group (RFC 3526's MODP-14, Config.crypto_group "modp14") puts every
// threshold share issue, Chaum-Pedersen check and Lagrange combine on
// 2048-bit exponentiations with 2047-bit exponents.  On the 13-core
// host of a v5e chip CPython's pow() takes 15.7 ms for one, OpenSSL's
// constant-time ladder 2.3 ms (PERF.md section 6).  The 256-bit kernel
// (native/modpow256.cpp) serves no such width.
//
// libcrypto is resolved with dlopen at first use, as
// native/sha256rows.cpp does, so the build needs no -dev headers;
// where it cannot be found every call returns -1 and the Python side
// takes its pow() fallback.
//
// Paths: a `pow` row is BN_mod_exp_mont_consttime, since an issue's
// exponent is a secret key share; a `dual_pow` row, u1^e1 * u2^e2, is
// BN_mod_exp2_mont (one interleaved ladder), since a Chaum-Pedersen
// check's exponents are the proof's public response and challenge.
// One Montgomery context per modulus, built once and shared read-only
// by every thread; a BN_CTX and the row's numbers per thread.
//
// Layout: every value crosses the ABI as a big-endian row; bases,
// results and the modulus are `width` bytes (the modulus's own byte
// width), exponents `exp_width` bytes.  Rows are independent, so a
// batch splits over threads (ctypes releases the GIL for the call).
//
// Beside the ladders, BN_kronecker: the symbol (x/p) a row, at gcd
// speed, for the subgroup check of a safe-prime group (ops/tpke.py
// is_group_element: Euler's criterion).  It is resolved on its own,
// so a libcrypto without it still serves the exponentiations.

#include <sched.h>

#include <dlfcn.h>

#include <cmath>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <string>
#include <system_error>
#include <thread>
#include <utility>
#include <vector>

namespace {

// OpenSSL's BN calls, as opaque pointers.
typedef void* (*bn_new_fn)();
typedef void (*bn_free_fn)(void*);
typedef void* (*bn_bin2bn_fn)(const unsigned char*, int, void*);
typedef int (*bn_bn2binpad_fn)(const void*, unsigned char*, int);
typedef void* (*ctx_new_fn)();
typedef void (*ctx_free_fn)(void*);
typedef void* (*mont_new_fn)();
typedef int (*mont_set_fn)(void*, const void*, void*);
typedef int (*exp_consttime_fn)(void*, const void*, const void*,
                                const void*, void*, void*);
typedef int (*exp2_fn)(void*, const void*, const void*, const void*,
                       const void*, const void*, void*, void*);
typedef int (*mod_mul_fn)(void*, const void*, const void*, const void*,
                          void*);
typedef int (*kronecker_fn)(const void*, const void*, void*);

struct Bn {
    bool ok = false;
    bn_new_fn bn_new = nullptr;
    bn_free_fn bn_free = nullptr;
    bn_bin2bn_fn bin2bn = nullptr;
    bn_bn2binpad_fn bn2binpad = nullptr;
    ctx_new_fn ctx_new = nullptr;
    ctx_free_fn ctx_free = nullptr;
    mont_new_fn mont_new = nullptr;
    mont_set_fn mont_set = nullptr;
    exp_consttime_fn exp_consttime = nullptr;
    exp2_fn exp2 = nullptr;
    mod_mul_fn mod_mul = nullptr;
    kronecker_fn kronecker = nullptr;  // optional: null where missing
};

template <typename T>
bool bind(void* lib, const char* name, T& slot) {
    slot = reinterpret_cast<T>(dlsym(lib, name));
    return slot != nullptr;
}

Bn resolve() {
    Bn b;
    for (const char* name :
         {"libcrypto.so.3", "libcrypto.so.1.1", "libcrypto.so"}) {
        void* lib = dlopen(name, RTLD_LAZY | RTLD_GLOBAL);
        if (!lib) continue;
        b.ok = bind(lib, "BN_new", b.bn_new) &&
               bind(lib, "BN_free", b.bn_free) &&
               bind(lib, "BN_bin2bn", b.bin2bn) &&
               bind(lib, "BN_bn2binpad", b.bn2binpad) &&
               bind(lib, "BN_CTX_new", b.ctx_new) &&
               bind(lib, "BN_CTX_free", b.ctx_free) &&
               bind(lib, "BN_MONT_CTX_new", b.mont_new) &&
               bind(lib, "BN_MONT_CTX_set", b.mont_set) &&
               bind(lib, "BN_mod_exp_mont_consttime", b.exp_consttime) &&
               bind(lib, "BN_mod_exp2_mont", b.exp2) &&
               bind(lib, "BN_mod_mul", b.mod_mul);
        if (b.ok) {
            bind(lib, "BN_kronecker", b.kronecker);
            break;
        }
    }
    return b;
}

const Bn& bn() {
    static const Bn b = resolve();
    return b;
}

// A modulus with its Montgomery context.  Contexts live as long as the
// process: a deployment names one or two groups.
struct Modulus {
    void* n = nullptr;
    void* mont = nullptr;
};

Modulus modulus_of(const uint8_t* mod, int64_t width) {
    static std::mutex lock;
    static std::vector<std::pair<std::string, Modulus>> known;
    std::string key(reinterpret_cast<const char*>(mod), size_t(width));
    std::lock_guard<std::mutex> hold(lock);
    for (const auto& row : known)
        if (row.first == key) return row.second;
    const Bn& b = bn();
    Modulus m;
    m.n = b.bin2bn(mod, int(width), nullptr);
    m.mont = b.mont_new();
    void* ctx = b.ctx_new();
    bool ok = m.n && m.mont && ctx && b.mont_set(m.mont, m.n, ctx) == 1;
    if (ctx) b.ctx_free(ctx);
    if (!ok) return Modulus{};  // leaks a failed set-up's numbers, once
    known.emplace_back(key, m);
    return m;
}

// One call's rows: `b` (bases or u1), `e` (exponents or e1), and for a
// dual call `b2` / `e2`; null b2 means a single exponentiation.
struct Rows {
    const uint8_t* b;
    const uint8_t* e;
    const uint8_t* b2;
    const uint8_t* e2;
    int64_t exp_width;
    int64_t width;
    uint8_t* out;
    int64_t m;
};

bool is_zero(const uint8_t* row, int64_t width) {
    for (int64_t k = 0; k < width; k++)
        if (row[k]) return false;
    return true;
}

// Rows [lo, hi) on the calling thread; false where OpenSSL failed.
bool run_range(const Modulus& mod, const Rows& r, int64_t lo, int64_t hi) {
    const Bn& b = bn();
    void* ctx = b.ctx_new();
    void* x = b.bn_new();
    void* y = b.bn_new();
    void* x2 = b.bn_new();
    void* y2 = b.bn_new();
    void* out = b.bn_new();
    void* out2 = b.bn_new();
    bool ok = ctx && x && y && x2 && y2 && out && out2;
    const int w = int(r.width), ew = int(r.exp_width);
    for (int64_t i = lo; ok && i < hi; i++) {
        ok = b.bin2bn(r.b + i * w, w, x) && b.bin2bn(r.e + i * ew, ew, y);
        if (ok && r.b2) {
            ok = b.bin2bn(r.b2 + i * w, w, x2) &&
                 b.bin2bn(r.e2 + i * ew, ew, y2);
            // the interleaved ladder reads a zero base as a zero
            // result whatever its exponent, where 0^0 is 1: such a row
            // takes two ladders and a product
            if (ok && (is_zero(r.b + i * w, w) || is_zero(r.b2 + i * w, w))) {
                ok = b.exp_consttime(out, x, y, mod.n, ctx, mod.mont) == 1 &&
                     b.exp_consttime(out2, x2, y2, mod.n, ctx, mod.mont) ==
                         1 &&
                     b.mod_mul(out, out, out2, mod.n, ctx) == 1;
            } else if (ok) {
                ok = b.exp2(out, x, y, x2, y2, mod.n, ctx, mod.mont) == 1;
            }
        } else if (ok) {
            ok = b.exp_consttime(out, x, y, mod.n, ctx, mod.mont) == 1;
        }
        ok = ok && b.bn2binpad(out, r.out + i * w, w) == w;
    }
    for (void* v : {x, y, x2, y2, out, out2})
        if (v) b.bn_free(v);
    if (ctx) b.ctx_free(ctx);
    return ok;
}

// Threads for a call.  A 2048-bit row with a 2047-bit exponent takes
// 2.3 ms on one core of the chip's host; starting and joining a
// thread there costs 75-200 us (PERF.md section 6).  W ns of
// work on T threads take about W/T + (T-1)*S, least at T = sqrt(W/S);
// at this width that is one thread a row up to the cores there are.
// A row's cost is reckoned from the widths: ~1.1 ns a 64-bit limb
// product, (width/8)^2 of them an exponent bit.
constexpr double kThreadStartNs = 150e3;
constexpr double kNsPerLimbProduct = 1.1;
constexpr int kMaxThreads = 16;

int cores() {
    cpu_set_t set;
    if (sched_getaffinity(0, sizeof(set), &set) == 0) {
        int n = CPU_COUNT(&set);
        if (n > 0) return n;
    }
    unsigned hw = std::thread::hardware_concurrency();
    return hw ? int(hw) : 1;
}

int auto_threads(const Rows& r) {
    double limbs = double(r.width + 7) / 8.0;
    double row_ns = kNsPerLimbProduct * limbs * limbs * 8.0 * double(r.exp_width);
    if (r.b2) row_ns *= 1.3;  // the interleaved ladder: a multiply more a bit
    int64_t t = int64_t(std::sqrt(double(r.m) * row_ns / kThreadStartNs));
    int cap = cores();
    if (cap > kMaxThreads) cap = kMaxThreads;
    if (t > cap) t = cap;
    return t < 1 ? 1 : int(t);
}

// The rows on `threads` threads (0: as the work calls for); returns
// the number that ran, or -1 where libcrypto is missing or failed.
int run(const uint8_t* mod, const Rows& r, int threads) {
    if (!bn().ok) return -1;
    if (r.m == 0) return 1;
    Modulus m = modulus_of(mod, r.width);
    if (!m.mont) return -1;
    if (threads <= 0) threads = auto_threads(r);
    if (threads > r.m) threads = int(r.m);
    if (threads <= 1) return run_range(m, r, 0, r.m) ? 1 : -1;
    int64_t chunk = (r.m + threads - 1) / threads;
    std::vector<std::thread> pool;
    std::vector<char> ok(size_t(threads), 1);
    pool.reserve(size_t(threads - 1));
    int ran = 1;
    int64_t lo = chunk;  // the calling thread takes the first chunk
    for (; lo < r.m; lo += chunk) {
        int64_t hi = lo + chunk < r.m ? lo + chunk : r.m;
        char* slot = &ok[size_t(ran)];
        try {
            pool.emplace_back([&m, &r, lo, hi, slot] {
                *slot = run_range(m, r, lo, hi);
            });
        } catch (const std::system_error&) {
            break;  // no thread to be had: the caller runs the rest
        }
        ran++;
    }
    ok[0] = run_range(m, r, 0, chunk < r.m ? chunk : r.m);
    if (lo < r.m) ok[0] = ok[0] && run_range(m, r, lo, r.m);
    for (auto& th : pool) th.join();
    for (char v : ok)
        if (!v) return -1;
    return ran;
}

}  // namespace

extern "C" {

// out[i] = base[i]^exp[i] mod mod, constant time in the exponent.
int modpow_wide_batch(const uint8_t* base, const uint8_t* exp,
                      int64_t exp_width, const uint8_t* mod, int64_t width,
                      uint8_t* out, int64_t m, int threads) {
    return run(mod, Rows{base, exp, nullptr, nullptr, exp_width, width, out,
                         m},
               threads);
}

// out[i] = u1[i]^e1[i] * u2[i]^e2[i] mod mod (public exponents).
int dualpow_wide_batch(const uint8_t* u1, const uint8_t* e1,
                       const uint8_t* u2, const uint8_t* e2,
                       int64_t exp_width, const uint8_t* mod, int64_t width,
                       uint8_t* out, int64_t m, int threads) {
    return run(mod, Rows{u1, e1, u2, e2, exp_width, width, out, m}, threads);
}

// out[i] = the Kronecker symbol (row i / mod), -1, 0 or 1, of m
// big-endian `width`-byte rows, on the calling thread (a round's
// subgroup checks are a few hundred rows of ~0.2 ms); returns 1, or
// -1 where libcrypto or its BN_kronecker is missing or failed.
int kronecker_wide_batch(const uint8_t* rows, int64_t width,
                         const uint8_t* mod, int8_t* out, int64_t m) {
    const Bn& b = bn();
    if (!b.ok || !b.kronecker) return -1;
    if (m == 0) return 1;
    void* ctx = b.ctx_new();
    void* n = b.bin2bn(mod, int(width), nullptr);
    void* x = b.bn_new();
    bool ok = ctx && n && x;
    for (int64_t i = 0; ok && i < m; i++) {
        ok = b.bin2bn(rows + i * width, int(width), x) != nullptr;
        int k = ok ? b.kronecker(x, n, ctx) : -2;
        ok = k >= -1 && k <= 1;  // -2: OpenSSL's error
        out[i] = int8_t(k);
    }
    for (void* v : {n, x})
        if (v) b.bn_free(v);
    if (ctx) b.ctx_free(ctx);
    return ok ? 1 : -1;
}

// 1 where libcrypto's BN calls resolved.
int modpow_wide_available() { return bn().ok ? 1 : 0; }

int modpow_wide_selftest() {
    if (!bn().ok) return 0;  // nothing to test: the callers fall back
    // mod 1000003 (odd, 3 bytes): 2^10 = 1024; 3^5 * 5^3 = 30375;
    // 7^0 = 1; rows on one thread and then split over two;
    // the fourth row 0^0 * 5^3 = 125 (the zero base's own path)
    const uint8_t mod[3] = {0x0f, 0x42, 0x43};
    const uint8_t base[12] = {0, 0, 2, 0, 0, 3, 0, 0, 7, 0, 0, 0};
    const uint8_t exp[4] = {10, 5, 0, 0};
    const uint8_t u2[12] = {0, 0, 5, 0, 0, 5, 0, 0, 5, 0, 0, 5};
    const uint8_t e2[4] = {0, 3, 0, 3};
    const uint8_t want_pow[12] = {0, 4, 0, 0, 0, 243, 0, 0, 1, 0, 0, 1};
    const uint8_t want_dual[12] = {0, 4, 0, 0, 0x76, 0xa7,
                                   0, 0, 1, 0, 0, 125};
    uint8_t got[12];
    for (int threads : {1, 2}) {
        std::memset(got, 0xff, sizeof(got));
        if (modpow_wide_batch(base, exp, 1, mod, 3, got, 4, threads) < 1)
            return 1;
        if (std::memcmp(got, want_pow, 12) != 0) return 2;
        if (dualpow_wide_batch(base, exp, u2, e2, 1, mod, 3, got, 4,
                               threads) < 1)
            return 3;
        if (std::memcmp(got, want_dual, 12) != 0) return 4;
    }
    if (!bn().kronecker) return 0;  // the callers take pow's path
    // (x / 1000003) for x = 0, 1, 2, 4, 1000002: 1000003 is prime and
    // 3 mod 8, so 2 is a non-residue and -1 too
    const uint8_t xs[15] = {0, 0, 0, 0, 0, 1, 0, 0, 2,
                            0, 0, 4, 0x0f, 0x42, 0x42};
    const int8_t want_k[5] = {0, 1, -1, 1, -1};
    int8_t sym[5] = {9, 9, 9, 9, 9};
    if (kronecker_wide_batch(xs, 3, mod, sym, 5) != 1) return 5;
    if (std::memcmp(sym, want_k, 5) != 0) return 6;
    return 0;
}

}  // extern "C"
