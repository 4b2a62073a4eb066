// Batched 256-bit modular exponentiation (Montgomery, 4x64-bit limbs).
//
// The host-CPU twin of ops/modmath.py's lazy-carry Montgomery TPU
// kernels: the threshold-crypto plane (Chaum-Pedersen share
// verification for TPKE decryption and the BBA common coin — the
// reference's "4N^2 signature sharings per node" cost model,
// docs/HONEYBADGER-EN.md:94) is thousands of independent 256-bit
// modexps per epoch.  CPython's pow() costs ~140 us per 256-bit
// exponentiation; this kernel runs the same math in ~10 us, giving the
// 'cpu'/'cpp' backends an honest native baseline (VERDICT round-2
// item 7) and keeping the live CPU protocol path off the python
// bignum wall.
//
// Beside the exponentiations, the scalar field: modreduce256_batch and
// muladdmod256_batch do a Chaum-Pedersen proof's arithmetic mod q (nonce
// and challenge reduction, z = w + e*s) on rows of bytes, so that a
// wave of ~N^2 proofs costs no Python bigint each (ops/tpke.py's share
// columns).
//
// Conventions: every value crosses the ABI as 32-byte little-endian
// (4 u64 limbs); the modulus must be odd (Montgomery requirement) and
// may be any 256-bit odd integer — the group parameters are inputs,
// not compile-time constants, so alternate primes (ops/modmath.py's
// documented group seam) reuse the same kernel.

#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

typedef uint64_t u64;
typedef unsigned __int128 u128;

namespace {

struct Ctx {
    u64 n[4];    // modulus
    u64 n0inv;   // -n^-1 mod 2^64
    u64 r2[4];   // R^2 mod n, R = 2^256
    u64 one_m[4];  // R mod n (Montgomery 1)
};

inline bool geq(const u64 a[4], const u64 b[4]) {
    for (int i = 3; i >= 0; --i) {
        if (a[i] != b[i]) return a[i] > b[i];
    }
    return true;
}

inline void sub(u64 a[4], const u64 b[4]) {
    u128 borrow = 0;
    for (int i = 0; i < 4; ++i) {
        u128 d = (u128)a[i] - b[i] - borrow;
        a[i] = (u64)d;
        borrow = (d >> 64) & 1;
    }
}

// CIOS Montgomery product: out = a*b*R^-1 mod n.
inline void mont_mul(const Ctx& c, const u64 a[4], const u64 b[4],
                     u64 out[4]) {
    u64 t[6] = {0, 0, 0, 0, 0, 0};
    for (int i = 0; i < 4; ++i) {
        u128 carry = 0;
        for (int j = 0; j < 4; ++j) {
            u128 s = (u128)a[i] * b[j] + t[j] + carry;
            t[j] = (u64)s;
            carry = s >> 64;
        }
        u128 s = (u128)t[4] + carry;
        t[4] = (u64)s;
        t[5] = (u64)(s >> 64);

        u64 m = t[0] * c.n0inv;
        carry = ((u128)m * c.n[0] + t[0]) >> 64;
        for (int j = 1; j < 4; ++j) {
            u128 s2 = (u128)m * c.n[j] + t[j] + carry;
            t[j - 1] = (u64)s2;
            carry = s2 >> 64;
        }
        s = (u128)t[4] + carry;
        t[3] = (u64)s;
        t[4] = t[5] + (u64)(s >> 64);
    }
    u64 r[4] = {t[0], t[1], t[2], t[3]};
    if (t[4] || geq(r, c.n)) sub(r, c.n);
    memcpy(out, r, sizeof(r));
}

void ctx_init(Ctx& c, const u64 n[4]) {
    memcpy(c.n, n, sizeof(c.n));
    // Newton iteration for n^-1 mod 2^64 (n odd), then negate.
    u64 inv = 1;
    for (int i = 0; i < 6; ++i) inv *= 2 - c.n[0] * inv;
    c.n0inv = (u64)(0 - inv);
    // R mod n by 256 doublings of 1; R^2 mod n by 256 more.
    u64 r[4] = {1, 0, 0, 0};
    for (int i = 0; i < 256; ++i) {
        u64 carry = r[3] >> 63;
        r[3] = (r[3] << 1) | (r[2] >> 63);
        r[2] = (r[2] << 1) | (r[1] >> 63);
        r[1] = (r[1] << 1) | (r[0] >> 63);
        r[0] <<= 1;
        if (carry || geq(r, c.n)) sub(r, c.n);
    }
    memcpy(c.one_m, r, sizeof(r));
    u64 r2[4];
    memcpy(r2, r, sizeof(r2));
    for (int i = 0; i < 256; ++i) {
        u64 carry = r2[3] >> 63;
        r2[3] = (r2[3] << 1) | (r2[2] >> 63);
        r2[2] = (r2[2] << 1) | (r2[1] >> 63);
        r2[1] = (r2[1] << 1) | (r2[0] >> 63);
        r2[0] <<= 1;
        if (carry || geq(r2, c.n)) sub(r2, c.n);
    }
    memcpy(c.r2, r2, sizeof(r2));
}

inline int exp_bit(const u64 e[4], int t) {
    return (int)((e[t >> 6] >> (t & 63)) & 1);
}

inline int exp_top_bit(const u64 e[4]) {
    for (int t = 255; t >= 0; --t)
        if (exp_bit(e, t)) return t;
    return -1;
}

// base^e mod n, 4-bit fixed window.
void mod_pow(const Ctx& c, const u64 base[4], const u64 e[4], u64 out[4]) {
    u64 table[16][4];
    memcpy(table[0], c.one_m, 32);
    mont_mul(c, base, c.r2, table[1]);  // to Montgomery
    for (int i = 2; i < 16; ++i) mont_mul(c, table[i - 1], table[1], table[i]);
    u64 acc[4];
    memcpy(acc, c.one_m, 32);
    int top = exp_top_bit(e);
    // start at the highest 4-aligned window covering bit `top`
    // (squaring Montgomery-one is a fixed point, so the first
    // window's four squarings are harmless)
    for (int w = (top < 0 ? -1 : top / 4); w >= 0; --w) {
        mont_mul(c, acc, acc, acc);
        mont_mul(c, acc, acc, acc);
        mont_mul(c, acc, acc, acc);
        mont_mul(c, acc, acc, acc);
        int idx = (exp_bit(e, 4 * w + 3) << 3) | (exp_bit(e, 4 * w + 2) << 2) |
                  (exp_bit(e, 4 * w + 1) << 1) | exp_bit(e, 4 * w);
        if (idx) mont_mul(c, acc, table[idx], acc);
    }
    u64 one[4] = {1, 0, 0, 0};
    mont_mul(c, acc, one, out);  // from Montgomery
}

// u1^e1 * u2^e2 mod n, Shamir's trick (the Chaum-Pedersen shape).
void dual_pow(const Ctx& c, const u64 u1[4], const u64 e1[4],
              const u64 u2[4], const u64 e2[4], u64 out[4]) {
    u64 t1[4], t2[4], t12[4];
    mont_mul(c, u1, c.r2, t1);
    mont_mul(c, u2, c.r2, t2);
    mont_mul(c, t1, t2, t12);
    u64 acc[4];
    memcpy(acc, c.one_m, 32);
    int top1 = exp_top_bit(e1), top2 = exp_top_bit(e2);
    int top = top1 > top2 ? top1 : top2;
    for (int t = top; t >= 0; --t) {
        mont_mul(c, acc, acc, acc);
        int idx = exp_bit(e1, t) | (exp_bit(e2, t) << 1);
        if (idx == 1) mont_mul(c, acc, t1, acc);
        else if (idx == 2) mont_mul(c, acc, t2, acc);
        else if (idx == 3) mont_mul(c, acc, t12, acc);
    }
    u64 one[4] = {1, 0, 0, 0};
    mont_mul(c, acc, one, out);
}

// x mod n for any x < R = 2^256: into the Montgomery domain and out.
inline void mod_n(const Ctx& c, const u64 x[4], u64 out[4]) {
    static const u64 kOne[4] = {1, 0, 0, 0};
    u64 xm[4];
    mont_mul(c, x, c.r2, xm);
    mont_mul(c, xm, kOne, out);
}

// (a + b) mod n for a, b < n.
inline void add_mod(const Ctx& c, const u64 a[4], const u64 b[4],
                    u64 out[4]) {
    u64 r[4];
    u128 carry = 0;
    for (int i = 0; i < 4; ++i) {
        u128 s = (u128)a[i] + b[i] + carry;
        r[i] = (u64)s;
        carry = s >> 64;
    }
    if (carry || geq(r, c.n)) sub(r, c.n);
    memcpy(out, r, sizeof(r));
}

// Independent exponentiations parallelize trivially; threading kicks
// in above a batch-size floor where spawn cost (~20 us/thread)
// amortizes.  ctypes releases the GIL for the whole call.
constexpr int kParallelFloor = 64;

template <typename F>
void run_batch(int b, F&& body) {
    unsigned hw = std::thread::hardware_concurrency();
    int threads = (int)(hw ? hw : 1);
    if (threads > 16) threads = 16;
    if (b < kParallelFloor || threads <= 1) {
        body(0, b);
        return;
    }
    if (threads > b) threads = b;
    std::vector<std::thread> pool;
    int chunk = (b + threads - 1) / threads;
    for (int t = 0; t < threads; ++t) {
        int lo = t * chunk, hi = lo + chunk < b ? lo + chunk : b;
        if (lo >= hi) break;
        pool.emplace_back([&body, lo, hi] { body(lo, hi); });
    }
    for (auto& th : pool) th.join();
}

}  // namespace

extern "C" {

// bases/exps/out: b rows of 32-byte little-endian values; mod: one
// 32-byte odd modulus shared by the whole batch.
void modpow256_batch(const uint8_t* bases, const uint8_t* exps,
                     const uint8_t* mod, uint8_t* out, int b) {
    Ctx c;
    u64 n[4];
    memcpy(n, mod, 32);
    ctx_init(c, n);
    run_batch(b, [&](int lo, int hi) {
        for (int i = lo; i < hi; ++i) {
            u64 base[4], e[4], r[4];
            memcpy(base, bases + 32 * i, 32);
            memcpy(e, exps + 32 * i, 32);
            mod_pow(c, base, e, r);
            memcpy(out + 32 * i, r, 32);
        }
    });
}

void dualpow256_batch(const uint8_t* u1, const uint8_t* e1,
                      const uint8_t* u2, const uint8_t* e2,
                      const uint8_t* mod, uint8_t* out, int b) {
    Ctx c;
    u64 n[4];
    memcpy(n, mod, 32);
    ctx_init(c, n);
    run_batch(b, [&](int lo, int hi) {
        for (int i = lo; i < hi; ++i) {
            u64 a[4], x[4], bb[4], y[4], r[4];
            memcpy(a, u1 + 32 * i, 32);
            memcpy(x, e1 + 32 * i, 32);
            memcpy(bb, u2 + 32 * i, 32);
            memcpy(y, e2 + 32 * i, 32);
            dual_pow(c, a, x, bb, y, r);
            memcpy(out + 32 * i, r, 32);
        }
    });
}

// The scalar field beside the group: the arithmetic mod q that a
// Chaum-Pedersen proof's nonce, challenge and response need, on rows
// of bytes (ops/tpke.py's share columns), so that a wave's ~N^2
// proofs cost no Python bigint each.

// A row costs a few Montgomery products (~0.1-0.3 us), a wave a few
// milliseconds: one thread.  (Spawning run_batch's pool for every call
// cost more than the arithmetic: measured on the chip's host.)

// in: b rows of `width` (1..64) little-endian bytes; out: b rows of
// 32 little-endian bytes, in mod n.  x = hi*R + lo, and hi*R mod n is
// mont_mul(hi, R^2).
void modreduce256_batch(const uint8_t* in, int width, const uint8_t* mod,
                        uint8_t* out, int b) {
    if (width < 1 || width > 64) return;
    Ctx c;
    u64 n[4];
    memcpy(n, mod, 32);
    ctx_init(c, n);
    for (int i = 0; i < b; ++i) {
        uint8_t buf[64] = {0};
        memcpy(buf, in + (size_t)width * i, width);
        u64 lo[4], hi[4], a[4], r[4];
        memcpy(lo, buf, 32);
        memcpy(hi, buf + 32, 32);
        mod_n(c, lo, a);
        if (width > 32) {
            mont_mul(c, hi, c.r2, r);
            add_mod(c, a, r, a);
        }
        memcpy(out + 32 * i, a, 32);
    }
}

// out = (a*b + c) mod n over b_rows rows of 32 little-endian bytes;
// any a, b, c < 2^256.
void muladdmod256_batch(const uint8_t* a, const uint8_t* b,
                        const uint8_t* c_in, const uint8_t* mod,
                        uint8_t* out, int b_rows) {
    Ctx c;
    u64 n[4];
    memcpy(n, mod, 32);
    ctx_init(c, n);
    for (int i = 0; i < b_rows; ++i) {
        u64 x[4], y[4], w[4], xm[4], r[4];
        memcpy(x, a + 32 * i, 32);
        memcpy(y, b + 32 * i, 32);
        memcpy(w, c_in + 32 * i, 32);
        if (geq(y, c.n)) mod_n(c, y, y);
        if (geq(w, c.n)) mod_n(c, w, w);
        mont_mul(c, x, c.r2, xm);   // x*R mod n
        mont_mul(c, xm, y, r);      // x*y mod n
        add_mod(c, r, w, r);
        memcpy(out + 32 * i, r, 32);
    }
}

int modpow256_selftest() {
    // n = 1000003 (odd), 2^20 mod n = 48573
    uint8_t n[32] = {0}, base[32] = {0}, e[32] = {0}, out[32] = {0};
    u64 nn = 1000003;
    memcpy(n, &nn, 8);
    base[0] = 2;
    e[0] = 20;
    modpow256_batch(base, e, n, out, 1);
    u64 got;
    memcpy(&got, out, 8);
    if (got != 48573) return 1;
    // dual: 3^7 * 5^4 mod 1000003 = 2187 * 625 mod 1000003 = 1366875
    // mod 1000003 = 366872
    uint8_t u1[32] = {0}, e1[32] = {0}, u2[32] = {0}, e2[32] = {0};
    u1[0] = 3; e1[0] = 7; u2[0] = 5; e2[0] = 4;
    dualpow256_batch(u1, e1, u2, e2, n, out, 1);
    memcpy(&got, out, 8);
    if (got != 366872) return 2;
    // e = 0 -> 1
    memset(e, 0, 32);
    modpow256_batch(base, e, n, out, 1);
    memcpy(&got, out, 8);
    if (got != 1) return 3;
    // scalars: 2^64 mod n = 16 * (2^20)^3 mod n = 16 * 48573^3 mod n
    u64 p3 = (u64)48573 * 48573 % nn * 48573 % nn * 16 % nn;
    uint8_t wide[40] = {0};
    wide[0] = 5;
    wide[8] = 1;  // 2^64 + 5
    modreduce256_batch(wide, 40, n, out, 1);
    memcpy(&got, out, 8);
    if (got != (p3 + 5) % nn) return 4;
    memset(wide, 0xff, 40);  // 2^320 - 1 = (2^64)^5 - 1
    u64 p5 = p3 * p3 % nn * p3 % nn * p3 % nn * p3 % nn;
    modreduce256_batch(wide, 40, n, out, 1);
    memcpy(&got, out, 8);
    if (got != (p5 + nn - 1) % nn) return 5;
    // 123456789 * 987654321 + 55 mod 1000003
    uint8_t ma[32] = {0}, mb[32] = {0}, mc[32] = {0};
    u64 va = 123456789, vb = 987654321, vc = 55;
    memcpy(ma, &va, 8); memcpy(mb, &vb, 8); memcpy(mc, &vc, 8);
    muladdmod256_batch(ma, mb, mc, n, out, 1);
    memcpy(&got, out, 8);
    if (got != ((va % nn) * (vb % nn) + vc) % nn) return 6;
    return 0;
}

}  // extern "C"
