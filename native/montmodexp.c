/* Modular exponentiation for the RSA hot path: the host tier's rows.
 *
 * CPython's big-int pow() is the write path's floor: one RSA-2048
 * CRT sign is two 1024-bit modexps at ~4 ms each, it holds the GIL
 * for the duration, and a 4-signs-per-write protocol tops out around
 * 25 writes/s/core no matter how few round trips the transport pays
 * (docs/PERFORMANCE.md "RSA floor").  This extension takes a batch of
 * rows in one call, releases the GIL while computing, and is loaded
 * opportunistically by bftkv_tpu/crypto/rsa.py (BFTKV_NATIVE_MODEXP=off
 * disables; the pure pow() path remains the semantics oracle, pinned
 * by differential tests in tests/test_rsa.py and test_host_batch.py).
 *
 * Engine: each row runs on libcrypto's Montgomery exponentiation
 * (its assembly Montgomery products), found at module init in the
 * libcrypto this process already has (CPython's _hashlib links it) and
 * bound with dlsym, so nothing links against it at build time.  A row
 * whose exponent is longer than 4 bytes (leading zero bytes aside) is
 * private material -- a CRT half, a CA fragment -- and takes
 * BN_mod_exp_mont_consttime; a public exponent takes BN_mod_exp_mont.
 * One Montgomery context per distinct modulus of a call.  Without the
 * library or a symbol, or for a row whose libcrypto call fails, the
 * row takes the fixed-width CIOS Montgomery loop below (4-bit window),
 * which gives the same answer.  The module attribute `engine` names
 * what the rows take ("libcrypto" or "cios").
 *
 * API:  powmod_many(width, ewidth, bases, exps, keys) -> bytes
 *   N rows, each with its own modulus, all big-endian: bases is
 *   N*width bytes (width a multiple of 8, base < mod), exps N*ewidth,
 *   keys N*(2*width+8) — per row mod || r2 || n0inv, with
 *   r2 = 2^(2*64*nlimbs) mod mod and n0inv = -mod^-1 mod 2^64 (the
 *   caller precomputes them, cached per key).  Returns N*width bytes.
 *   The GIL is released once for the whole call: the host tier's batch
 *   entry (crypto/rsa.py sign_many / verify_host_many; one row is the
 *   one-item form).
 *       powmod_many_cios(...) is the same call on the CIOS loop alone,
 *   for the tests that pin it; the loader never calls it.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <dlfcn.h>
#include <stdint.h>
#include <string.h>

typedef unsigned __int128 u128;
typedef uint64_t u64;

#define MAX_LIMBS 64 /* up to 4096-bit moduli */

/* little-endian limb arrays throughout */

static void be_to_limbs(const unsigned char *be, Py_ssize_t len, u64 *out,
                        int nlimbs) {
    memset(out, 0, (size_t)nlimbs * 8);
    for (Py_ssize_t i = 0; i < len; i++) {
        Py_ssize_t bit = (len - 1 - i);
        out[bit / 8] |= (u64)be[i] << (8 * (bit % 8));
    }
}

static void limbs_to_be(const u64 *in, int nlimbs, unsigned char *be) {
    for (int i = 0; i < nlimbs; i++) {
        u64 w = in[nlimbs - 1 - i];
        for (int b = 0; b < 8; b++)
            be[i * 8 + b] = (unsigned char)(w >> (8 * (7 - b)));
    }
}

static int geq(const u64 *a, const u64 *n, int L) {
    for (int i = L - 1; i >= 0; i--) {
        if (a[i] > n[i]) return 1;
        if (a[i] < n[i]) return 0;
    }
    return 1; /* equal */
}

static void sub_n(u64 *a, const u64 *n, int L) {
    u64 borrow = 0;
    for (int i = 0; i < L; i++) {
        u64 ni = n[i] + borrow;
        borrow = (ni < borrow) | (a[i] < ni);
        a[i] -= ni;
    }
}

/* CIOS Montgomery multiplication: t = a*b*R^-1 mod n (R = 2^(64L)).
 * Accumulator has L+2 limbs; result reduced to < n. */
static void mont_mul(const u64 *a, const u64 *b, const u64 *n, u64 n0inv,
                     int L, u64 *t /* L+2 scratch, output in t[0..L-1] */) {
    memset(t, 0, (size_t)(L + 2) * 8);
    for (int i = 0; i < L; i++) {
        u64 carry = 0;
        u64 ai = a[i];
        for (int j = 0; j < L; j++) {
            u128 s = (u128)ai * b[j] + t[j] + carry;
            t[j] = (u64)s;
            carry = (u64)(s >> 64);
        }
        u128 s = (u128)t[L] + carry;
        t[L] = (u64)s;
        t[L + 1] = (u64)(s >> 64);

        u64 m = t[0] * n0inv;
        s = (u128)m * n[0] + t[0];
        carry = (u64)(s >> 64);
        for (int j = 1; j < L; j++) {
            s = (u128)m * n[j] + t[j] + carry;
            t[j - 1] = (u64)s;
            carry = (u64)(s >> 64);
        }
        s = (u128)t[L] + carry;
        t[L - 1] = (u64)s;
        t[L] = t[L + 1] + (u64)(s >> 64);
        t[L + 1] = 0;
    }
    if (t[L] || geq(t, n, L)) sub_n(t, n, L);
}

/* x^e mod n for one row, limbs in and out; e is big-endian bytes.
 * Touches no Python object: it runs with the GIL released.  Leading zeros of e cost nothing (rows of one batch pad
 * their exponents to a common length). */
static void powmod_core(const u64 *x, const unsigned char *e,
                        Py_ssize_t elen, const u64 *n, const u64 *r2,
                        u64 n0inv, int L, u64 *acc /* L limbs out */) {
    u64 table[16][MAX_LIMBS];
    u64 one[MAX_LIMBS], t[MAX_LIMBS + 2];
    size_t bytes = (size_t)L * 8;

    while (elen > 0 && e[0] == 0) { e++; elen--; }
    memset(one, 0, bytes);
    one[0] = 1;
    /* table[1] = x in Montgomery form; table[0] = 1 in Mont form */
    mont_mul(x, r2, n, n0inv, L, t);
    memcpy(table[1], t, bytes);
    mont_mul(one, r2, n, n0inv, L, t);
    memcpy(table[0], t, bytes);
    memcpy(acc, table[0], bytes);

    if (elen <= 4) {
        /* A public exponent (65537: 16 squarings and one multiply):
         * bit by bit, since the window table alone costs 14 multiplies. */
        int started = 0;
        for (Py_ssize_t i = 0; i < elen; i++) {
            for (int bit = 7; bit >= 0; bit--) {
                int b = (e[i] >> bit) & 1;
                if (started) {
                    mont_mul(acc, acc, n, n0inv, L, t);
                    memcpy(acc, t, bytes);
                }
                if (b) {
                    if (started) {
                        mont_mul(acc, table[1], n, n0inv, L, t);
                        memcpy(acc, t, bytes);
                    } else {
                        memcpy(acc, table[1], bytes);
                        started = 1;
                    }
                }
            }
        }
    } else {
        for (int i = 2; i < 16; i++) {
            mont_mul(table[i - 1], table[1], n, n0inv, L, t);
            memcpy(table[i], t, bytes);
        }
        /* 4-bit windowed scan over the big-endian exponent bytes; the
         * leading byte is not 0, so at most one leading nibble is */
        int started = 0;
        for (Py_ssize_t i = 0; i < elen; i++) {
            unsigned char byte = e[i];
            for (int half = 0; half < 2; half++) {
                int w = half == 0 ? (byte >> 4) : (byte & 0xF);
                if (!started) {
                    if (w) {
                        started = 1;
                        memcpy(acc, table[w], bytes);
                    }
                    continue;
                }
                for (int s = 0; s < 4; s++) {
                    mont_mul(acc, acc, n, n0inv, L, t);
                    memcpy(acc, t, bytes);
                }
                if (w) {
                    mont_mul(acc, table[w], n, n0inv, L, t);
                    memcpy(acc, t, bytes);
                }
            }
        }
    }

    /* out of Montgomery form */
    mont_mul(acc, one, n, n0inv, L, t);
    memcpy(acc, t, bytes);
}

/* -- the libcrypto engine --------------------------------------------------
 * Opaque types and the prototypes of OpenSSL's BN API (stable across
 * 1.1 and 3.x); no header is needed. */

typedef struct bignum_st BIGNUM;
typedef struct bignum_ctx BN_CTX;
typedef struct bn_mont_ctx_st BN_MONT_CTX;
typedef int (*bn_exp_fn)(BIGNUM *r, const BIGNUM *a, const BIGNUM *p,
                         const BIGNUM *m, BN_CTX *ctx, BN_MONT_CTX *mont);

static struct {
    BN_CTX *(*ctx_new)(void);
    void (*ctx_free)(BN_CTX *);
    BIGNUM *(*bn_new)(void);
    void (*bn_free)(BIGNUM *);
    BIGNUM *(*bin2bn)(const unsigned char *, int, BIGNUM *);
    int (*bn2binpad)(const BIGNUM *, unsigned char *, int);
    BN_MONT_CTX *(*mont_new)(void);
    int (*mont_set)(BN_MONT_CTX *, const BIGNUM *, BN_CTX *);
    void (*mont_free)(BN_MONT_CTX *);
    bn_exp_fn exp_public, exp_secret;
} lc;
static int lc_ready;

static int lc_sym(void *h, const char *name, void *slot) {
    void *p = dlsym(h, name);
    if (p == NULL) return 0;
    memcpy(slot, &p, sizeof p); /* object pointer -> function pointer */
    return 1;
}

/* 1 when every symbol is bound.  The handle is never closed: the
 * library outlives the module. */
static int lc_load(void) {
    void *h = dlopen("libcrypto.so.3", RTLD_NOW | RTLD_NOLOAD);
    if (h == NULL) h = dlopen("libcrypto.so.3", RTLD_NOW);
    if (h == NULL) return 0;
    return lc_sym(h, "BN_CTX_new", &lc.ctx_new) &&
           lc_sym(h, "BN_CTX_free", &lc.ctx_free) &&
           lc_sym(h, "BN_new", &lc.bn_new) &&
           lc_sym(h, "BN_free", &lc.bn_free) &&
           lc_sym(h, "BN_bin2bn", &lc.bin2bn) &&
           lc_sym(h, "BN_bn2binpad", &lc.bn2binpad) &&
           lc_sym(h, "BN_MONT_CTX_new", &lc.mont_new) &&
           lc_sym(h, "BN_MONT_CTX_set", &lc.mont_set) &&
           lc_sym(h, "BN_MONT_CTX_free", &lc.mont_free) &&
           lc_sym(h, "BN_mod_exp_mont", &lc.exp_public) &&
           lc_sym(h, "BN_mod_exp_mont_consttime", &lc.exp_secret);
}

/* A call's Montgomery contexts, one per distinct modulus, replaced
 * round-robin past MONT_SLOTS.  Rows of one modulus need not be
 * neighbours: a client's CRT halves alternate p, q, p, q. */
#define MONT_SLOTS 16

typedef struct {
    const unsigned char *mod; /* the row's modulus bytes in `keys` */
    BIGNUM *n;
    BN_MONT_CTX *mont;
} mont_slot;

static mont_slot *mont_for(mont_slot *slots, int *used, int *next,
                           const unsigned char *mod, Py_ssize_t width,
                           BN_CTX *ctx) {
    for (int i = 0; i < *used; i++)
        if (slots[i].mod != NULL &&
            memcmp(slots[i].mod, mod, (size_t)width) == 0)
            return &slots[i];
    mont_slot *s;
    if (*used < MONT_SLOTS) {
        s = &slots[(*used)++];
        s->n = lc.bn_new();
        s->mont = lc.mont_new();
    } else {
        s = &slots[*next];
        *next = (*next + 1) % MONT_SLOTS;
    }
    s->mod = NULL;
    if (s->n == NULL || s->mont == NULL ||
        lc.bin2bn(mod, (int)width, s->n) == NULL ||
        !lc.mont_set(s->mont, s->n, ctx))
        return NULL;
    s->mod = mod;
    return s;
}

/* Rows through libcrypto; done[r] = 1 for each row it finished, the
 * rest are the caller's.  Touches no Python object. */
static void lc_rows(Py_ssize_t rows, Py_ssize_t width, Py_ssize_t ewidth,
                    const unsigned char *bases, const unsigned char *exps,
                    const unsigned char *keys, unsigned char *out,
                    unsigned char *done) {
    Py_ssize_t kw = 2 * width + 8;
    mont_slot slots[MONT_SLOTS];
    int used = 0, next = 0;
    BN_CTX *ctx = lc.ctx_new();
    BIGNUM *x = lc.bn_new(), *p = lc.bn_new(), *r = lc.bn_new();
    if (ctx != NULL && x != NULL && p != NULL && r != NULL) {
        for (Py_ssize_t i = 0; i < rows; i++) {
            const unsigned char *e = exps + i * ewidth;
            Py_ssize_t elen = ewidth;
            while (elen > 0 && e[0] == 0) { e++; elen--; }
            mont_slot *s = mont_for(slots, &used, &next, keys + i * kw,
                                    width, ctx);
            bn_exp_fn f = elen > 4 ? lc.exp_secret : lc.exp_public;
            done[i] = s != NULL &&
                      lc.bin2bn(bases + i * width, (int)width, x) != NULL &&
                      lc.bin2bn(e, (int)elen, p) != NULL &&
                      f(r, x, p, s->n, ctx, s->mont) &&
                      lc.bn2binpad(r, out + i * width, (int)width) == width;
        }
    }
    for (int i = 0; i < used; i++) {
        lc.bn_free(slots[i].n); /* both take NULL */
        lc.mont_free(slots[i].mont);
    }
    lc.bn_free(x);
    lc.bn_free(p);
    lc.bn_free(r);
    lc.ctx_free(ctx);
}

/* -- the batch entries ----------------------------------------------------- */

static PyObject *powmod_batch(PyObject *args, int use_lc) {
    Py_buffer base_b, exp_b, key_b;
    Py_ssize_t width, ewidth;
    if (!PyArg_ParseTuple(args, "nny*y*y*", &width, &ewidth, &base_b, &exp_b,
                          &key_b))
        return NULL;

    PyObject *ret = NULL;
    unsigned char *done = NULL; /* rows the libcrypto engine finished */
    int L = (int)(width / 8);
    Py_ssize_t rows = width > 0 ? base_b.len / width : 0;
    Py_ssize_t kw = 2 * width + 8;
    if (width <= 0 || width % 8 != 0 || L > MAX_LIMBS || ewidth <= 0 ||
        base_b.len != rows * width || exp_b.len != rows * ewidth ||
        key_b.len != rows * kw) {
        PyErr_SetString(PyExc_ValueError, "montmodexp: bad operand shape");
        goto done;
    }
    {
        const unsigned char *keys = (const unsigned char *)key_b.buf;
        for (Py_ssize_t r = 0; r < rows; r++) {
            if (!(keys[r * kw + width - 1] & 1)) {
                PyErr_SetString(PyExc_ValueError, "montmodexp: even modulus");
                goto done;
            }
        }
    }
    done = PyMem_Calloc(rows > 0 ? (size_t)rows : 1, 1);
    ret = done != NULL ? PyBytes_FromStringAndSize(NULL, rows * width)
                       : PyErr_NoMemory();
    if (ret == NULL) goto done;
    {
        const unsigned char *bases = (const unsigned char *)base_b.buf;
        const unsigned char *exps = (const unsigned char *)exp_b.buf;
        const unsigned char *keys = (const unsigned char *)key_b.buf;
        unsigned char *out = (unsigned char *)PyBytes_AS_STRING(ret);

        Py_BEGIN_ALLOW_THREADS;
        if (use_lc)
            lc_rows(rows, width, ewidth, bases, exps, keys, out, done);
        for (Py_ssize_t r = 0; r < rows; r++) {
            if (done[r]) continue;
            u64 n[MAX_LIMBS], x[MAX_LIMBS], r2[MAX_LIMBS], acc[MAX_LIMBS];
            u64 n0inv[1];
            const unsigned char *k = keys + r * kw;
            be_to_limbs(k, width, n, L);
            be_to_limbs(k + width, width, r2, L);
            be_to_limbs(k + 2 * width, 8, n0inv, 1);
            be_to_limbs(bases + r * width, width, x, L);
            powmod_core(x, exps + r * ewidth, ewidth, n, r2, n0inv[0], L,
                        acc);
            limbs_to_be(acc, L, out + r * width);
        }
        Py_END_ALLOW_THREADS;
    }

done:
    PyMem_Free(done);
    PyBuffer_Release(&base_b);
    PyBuffer_Release(&exp_b);
    PyBuffer_Release(&key_b);
    return ret;
}

static PyObject *py_powmod_many(PyObject *self, PyObject *args) {
    return powmod_batch(args, lc_ready);
}

static PyObject *py_powmod_many_cios(PyObject *self, PyObject *args) {
    return powmod_batch(args, 0);
}

static PyMethodDef Methods[] = {
    {"powmod_many", py_powmod_many, METH_VARARGS,
     "powmod_many(width, ewidth, bases, exps, keys) -> bytes (N rows packed "
     "big-endian; keys rows are mod || r2 || n0inv(8); one GIL release)"},
    {"powmod_many_cios", py_powmod_many_cios, METH_VARARGS,
     "powmod_many on the CIOS loop alone, whatever `engine` says"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_montmodexp",
    "batched Montgomery modexp (GIL-releasing)", -1, Methods,
};

PyMODINIT_FUNC PyInit__montmodexp(void) {
    PyObject *m = PyModule_Create(&moduledef);
    if (m == NULL) return NULL;
    lc_ready = lc_load();
    if (PyModule_AddStringConstant(m, "engine",
                                   lc_ready ? "libcrypto" : "cios") < 0) {
        Py_DECREF(m);
        return NULL;
    }
    return m;
}
