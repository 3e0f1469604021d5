"""Faults planted under the timed path, for the controls and the tests.

Each breaks one guarantee the configuration states, at the place where
the answer is produced.  The driver's runs never plant anything
(``--plant`` is for controls); a planted run must come out
``correct: false``.

Planted in the client facade the callers drive (``every`` comes from the
mix's ``controls``: a rate near the smallest loss the cell should see):

- ``lost_write``  every ``every``-th item of a write is acknowledged and
  never sent: an acknowledged write that no replica holds.
- ``wrong_read``  every ``every``-th value read comes back with one bit
  changed.

Planted inside the sidecar process, where the verdict is produced
(``sidecar_main.py`` installs and removes it on a control operation):

- ``accept_all``  the device verify chain skips the modexp and answers
  True for every row of a launch (under ``--rehearse``, where the CPU
  sidecar verifies on the host tier, the host oracle too: the batch form
  ``rsa.verify_host_many`` and the one-item ``rsa.verify_host``).  The
  daemons' items are all valid, so nothing they see changes; the forged
  items of ``tenant.py`` are accepted.

A kind that lives in a file (``benchmarks/kinds/``) may bring client-facade
plants of its own in ``_Planted``'s shape; ``plant`` takes a table that
holds them beside these.
"""

from __future__ import annotations


class _Planted:
    def __init__(self, api, every: int):
        self._api = api
        self._every = max(1, int(every))
        self._n = 0

    def __getattr__(self, name):
        return getattr(self._api, name)

    def _hit(self) -> bool:
        self._n += 1
        return self._n % self._every == 0


class LostWrite(_Planted):
    def write(self, variable, value, password=""):
        if not self._hit():
            self._api.write(variable, value, password)

    def write_many(self, items):
        keep = [not self._hit() for _ in items]
        sent = iter(self._api.write_many(
            [it for it, k in zip(items, keep) if k]
        ))
        return [next(sent) if k else None for k in keep]


class WrongRead(_Planted):
    def _bend(self, value):
        if isinstance(value, bytes) and value and self._hit():
            return value[:-1] + bytes([value[-1] ^ 1])
        return value

    def read(self, variable, password=""):
        return self._bend(self._api.read(variable, password))

    def read_many(self, variables):
        return [self._bend(v) for v in self._api.read_many(variables)]


PLANTS = {"lost_write": LostWrite, "wrong_read": WrongRead}
SIDECAR_PLANTS = ("accept_all",)


def plant(name: str, api, every: int = 7, table: dict = PLANTS):
    """``api`` with ``name`` planted under it (``""``: ``api`` itself).
    ``table`` is ``PLANTS``, or it with the plants of the mix's kinds."""
    if not name:
        return api
    if name not in table:
        raise ValueError(f"unknown plant {name!r}; known: {sorted(table)}")
    return table[name](api, every)


# -- inside the sidecar process ---------------------------------------------

_restore: list = []


def sidecar_plant(name: str, host_tier: bool) -> None:
    """Install ``name`` in this (the sidecar's) process."""
    if name not in SIDECAR_PLANTS:
        raise ValueError(f"unknown sidecar plant {name!r}")
    if _restore:
        raise RuntimeError("a sidecar plant is already installed")
    import numpy as np

    from bftkv_tpu.crypto import rsa
    from bftkv_tpu.ops import rns

    def all_true(_digits, _em, idxs, _rows):
        return np.ones((len(idxs),), dtype=bool)

    _restore.append((rns, "verify_e65537_rns_indexed",
                     rns.verify_e65537_rns_indexed))
    rns.verify_e65537_rns_indexed = all_true
    if host_tier:
        _restore.append((rsa, "verify_host", rsa.verify_host))
        rsa.verify_host = lambda *_a, **_k: True
        _restore.append((rsa, "verify_host_many", rsa.verify_host_many))
        rsa.verify_host_many = lambda items: [True] * len(items)


def sidecar_unplant() -> None:
    while _restore:
        module, attr, original = _restore.pop()
        setattr(module, attr, original)
