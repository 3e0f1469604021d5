"""Operation kind ``ca_issue``: one certificate of the threshold CA.

The deployment's ``threshold_ca`` (``configs/<config>.json``) is dealt
once a run, inside ``setup_s``: an RSA key made from ``--seed`` by the
reference's ``rsa_keygen``, handed to ``api.distribute`` of the first
user's client under the name ``ca-<seed>`` — the client deals it (k, n)
over the AUTH quorum, as ``bftrw ca`` does.  One call is one
certificate, as ``bftrw sign`` issues it: ``api.sign`` of a TBS drawn
from the caller's generator, then the certificate stored as one record
of the mix (a built-in ``insert`` beside the kind's own call).  The
operation is acknowledged when both halves are.

The judge takes EVERY certificate a call got back, warm calls included:
it has to verify under the CA's public key and to equal the PKCS#1 v1.5
signature of the undealt key byte for byte
(``ca_issue_reference.py``; nothing of the program is imported there).

The cell states its layout: ONE chip-owning sidecar takes the quorum's
fragment modexps.  A program that cannot send a replica's modexp there
would run the same calls on ten daemons' host ``pow`` — the hidden host
fallback, measured as if it were this deployment.  So the kind asks the
program when it is loaded, before any child starts, and refuses by name
(:func:`route_or_refuse`).  That is the issuing side, which uses the
program as ``one_call`` does; ``judge`` and the reference import nothing
of it.
"""

from __future__ import annotations

import random

from benchmarks.kinds import ca_issue_reference as reference
from benchmarks.plants import _Planted

KIND = "ca_issue"
LIMITS = [("ca_certs_bad", "<=", 0), ("ca_certs_checked", ">=", 1)]


def route_or_refuse(bits: int = 2048, n: int = 10) -> None:
    """The one question: does a ``--sidecar`` daemon's modexp leave for
    the sidecar, and does a device chain there hold a first-level
    fragment of a ``bits``-bit key dealt (., n) — a ``bits``-bit modulus
    under an exponent of up to ``2 * bits + ceil(log2 n) + 1`` bits.  The
    program answers through ``ops.modexp.remote_route``; one that lacks
    the function has answered."""
    try:
        from bftkv_tpu.ops import modexp as program_modexp

        held = program_modexp.remote_route(
            bits, 2 * bits + (n - 1).bit_length() + 1)
    except (ImportError, AttributeError):
        held = False
    if not held:
        raise RuntimeError(
            "this program runs a replica's fragment modexps in the replica, "
            "on the host: q10-ca2048 states one chip-owning sidecar that "
            "takes them (no ops.modexp.remote_route, or no device chain for "
            f"a {bits}-bit modulus under a first-level fragment's exponent)")


route_or_refuse()  # when the kind is loaded: kinds.load names what it raises


def prepare(ctx: dict) -> dict:
    from bftkv_tpu.crypto import rsa as program_rsa

    ca = ctx["config"]["threshold_ca"]
    bits = int(ca["rehearse_key_bits"] if ctx["rehearse"] else ca["key_bits"])
    key = reference.rsa_keygen(random.Random(f"{ctx['seed']}|ca"), bits)
    name = f"ca-{ctx['seed']}"
    ctx["clients"][0].distribute(name, program_rsa.PrivateKey(
        n=key.n, e=key.e, d=key.d, p=key.p, q=key.q))
    return {"name": name, "key": key, "tbs_bytes": int(ca["tbs_bytes"])}


def one_call(caller, state: dict, phase: str) -> list:
    from bftkv_tpu.crypto.threshold import ThresholdAlgo

    tbs = caller.rng.randbytes(state["tbs_bytes"])
    call = caller.new_call(KIND, [caller.rng.getrandbits(48)], [], phase)
    try:
        sig = caller.api.sign(state["name"], tbs, ThresholdAlgo.RSA, "sha256")
    except Exception as e:  # refused or lost: nothing to store
        call.errors = [repr(e)]
        return [call]
    call.values = [(tbs, sig)]
    stored = caller.builtin("insert", phase)
    failed = [e for e in stored.errors if e is not None]
    call.errors = [f"signed and not stored: {failed[0]}" if failed else None]
    return [call, stored]


def judge(calls: list, state: dict, ctx: dict) -> dict:
    key = state["key"]
    bad = checked = 0
    for call in calls:
        for tbs, sig in call.values:
            checked += 1
            bad += not (reference.rsa_verify(tbs, sig, key.n, key.e)
                        and sig == reference.rsa_sign(tbs, key))
    return {"ca_certs_bad": bad, "ca_certs_checked": checked}


class BentSignature(_Planted):
    """Every ``every``-th certificate comes back with one bit changed."""

    def sign(self, caname, tbs, algo, hash_name):
        sig = self._api.sign(caname, tbs, algo, hash_name)
        if self._hit():
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        return sig


PLANTS = {"ca_bent_signature": BentSignature}
