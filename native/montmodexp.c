/* Montgomery modular exponentiation for the RSA hot path.
 *
 * CPython's big-int pow() is the write path's floor: one RSA-2048
 * CRT sign is two 1024-bit modexps at ~4 ms each, it holds the GIL
 * for the duration, and a 4-signs-per-write protocol tops out around
 * 25 writes/s/core no matter how few round trips the transport pays
 * (docs/PERFORMANCE.md "RSA floor").  This extension implements the
 * same modexp as fixed-width CIOS Montgomery multiplication with a
 * 4-bit window, releases the GIL while computing, and is loaded
 * opportunistically by bftkv_tpu/crypto/rsa.py (BFTKV_NATIVE_MODEXP=off
 * disables; the pure pow() path remains the semantics oracle, pinned
 * by differential tests in tests/test_rsa.py).
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
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
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

static PyObject *py_powmod_many(PyObject *self, PyObject *args) {
    Py_buffer base_b, exp_b, key_b;
    Py_ssize_t width, ewidth;
    if (!PyArg_ParseTuple(args, "nny*y*y*", &width, &ewidth, &base_b, &exp_b,
                          &key_b))
        return NULL;

    PyObject *ret = NULL;
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
    ret = PyBytes_FromStringAndSize(NULL, rows * width);
    if (ret == NULL) goto done;
    {
        const unsigned char *bases = (const unsigned char *)base_b.buf;
        const unsigned char *exps = (const unsigned char *)exp_b.buf;
        const unsigned char *keys = (const unsigned char *)key_b.buf;
        unsigned char *out = (unsigned char *)PyBytes_AS_STRING(ret);

        Py_BEGIN_ALLOW_THREADS;
        for (Py_ssize_t r = 0; r < rows; r++) {
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
    PyBuffer_Release(&base_b);
    PyBuffer_Release(&exp_b);
    PyBuffer_Release(&key_b);
    return ret;
}

static PyMethodDef Methods[] = {
    {"powmod_many", py_powmod_many, METH_VARARGS,
     "powmod_many(width, ewidth, bases, exps, keys) -> bytes (N rows packed "
     "big-endian; keys rows are mod || r2 || n0inv(8); one GIL release)"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_montmodexp",
    "fixed-width Montgomery modexp (GIL-releasing)", -1, Methods,
};

PyMODINIT_FUNC PyInit__montmodexp(void) { return PyModule_Create(&moduledef); }
