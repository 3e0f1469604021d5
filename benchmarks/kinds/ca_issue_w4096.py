"""Operation kind ``ca_issue_w4096``: one certificate of a threshold CA
whose key is RSA-4096, a root CA's width.

Everything a call does is ``ca_issue``'s — ``prepare`` deals the key the
configuration's ``threshold_ca`` names (4,096 bits, made from ``--seed``
by the reference's ``rsa_keygen``), a call is ``api.sign`` of a TBS and
then one stored record, drawn as ``ca_issue`` draws them — under this
kind's own name, so that its numbers and its plant are its own.

A first-level fragment of a 4,096-bit key is a 4,096-bit modulus under
an exponent of up to 2 x 4,096 + 5 bits: a class no 12-bit residue base
holds.  The kind asks the program when it is loaded, before any child
starts, whether a ``--sidecar`` daemon's modexp of that class leaves for
the sidecar and finds a device chain there (``ca_issue.route_or_refuse``)
and refuses by name where it does not: such a program would run the
cell's fragment modexps on the host tier, which is not this deployment.

The judge checks EVERY certificate a call got back, warm calls included:
a signature of the key's byte length, below the modulus, that verifies
under the CA's public key.  RSA permutes Z_n, so with s < n that is the
one PKCS#1 v1.5 signature of the TBS.  On a sample drawn from the seed
it also re-signs with the UNDEALT key (``ca_issue_reference.rsa_sign``:
~50 ms a certificate at 4,096 bits in Python) and asks for the same
bytes, as ``ca_issue`` does for every certificate.
"""

from __future__ import annotations

import random

from benchmarks.kinds import ca_issue
from benchmarks.kinds import ca_issue_reference as reference

KIND = "ca_issue_w4096"
BITS = 4096
SAMPLE = 256  # certificates re-signed byte for byte a run
LIMITS = [("ca4096_certs_bad", "<=", 0), ("ca4096_certs_checked", ">=", 1),
          ("ca4096_certs_resigned", ">=", 1)]


def route_or_refuse() -> None:
    try:
        ca_issue.route_or_refuse(BITS)
    except RuntimeError:
        raise RuntimeError(
            "this program runs a replica's fragment modexps of a 4,096-bit "
            "CA key in the replica, on the host: q10-ca4096 states one "
            "chip-owning sidecar that takes them (no ops.modexp.remote_route, "
            "or no device chain for a 4,096-bit modulus under a first-level "
            "fragment's exponent)") from None


route_or_refuse()  # when the kind is loaded: kinds.load names what it raises


def prepare(ctx: dict) -> dict:
    state = ca_issue.prepare(ctx)
    state["seed"] = ctx["seed"]
    return state


def one_call(caller, state: dict, phase: str) -> list:
    calls = ca_issue.one_call(caller, state, phase)
    for call in calls:
        if call.kind == ca_issue.KIND:
            call.kind = KIND
    return calls


def judge(calls: list, state: dict, ctx: dict) -> dict:
    key = state["key"]
    size = (key.n.bit_length() + 7) // 8
    certs = [pair for call in calls for pair in call.values]
    sample = set(random.Random(f"{state['seed']}|ca-judge").sample(
        range(len(certs)), min(len(certs), SAMPLE)))
    bad = 0
    for i, (tbs, sig) in enumerate(certs):
        ok = (len(sig) == size and int.from_bytes(sig, "big") < key.n
              and reference.rsa_verify(tbs, sig, key.n, key.e))
        if ok and i in sample:
            ok = sig == reference.rsa_sign(tbs, key)
        bad += not ok
    return {"ca4096_certs_bad": bad, "ca4096_certs_checked": len(certs),
            "ca4096_certs_resigned": len(sample)}


PLANTS = {"ca_bent_signature_w4096": ca_issue.BentSignature}
