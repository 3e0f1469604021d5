"""Operation kinds that are files: ``benchmarks/kinds/<kind>.py``.

A share in a mix's ``ops`` that is not one of the generator's built-ins
(``insert``, ``update``, ``read``) names a file here, found by name as
readers are.  A kind is plain functions and two tables:

``LIMITS``
    The numbers its ``judge`` returns, each with its limit:
    ``[("certs_bad", "<=", 0), ("certs_checked", ">=", 1)]``.  Every
    number that decides ``correct`` is exact, so a limit is ``("<=", 0)``
    or a floor ``(">=", 1)`` and nothing else; a name that a built-in
    number or another kind has is refused.  Checked when the kind is
    loaded, before any child starts.

``prepare(ctx) -> state`` (optional)
    Once a run, after the clients are open and before the warm calls,
    inside ``setup_s``: what the deployment does before it serves.
    ``ctx`` holds ``clients`` (one per user of the configuration), ``config``,
    ``mix``, ``seed`` and ``rehearse``.  What it returns is handed to
    every later call of the kind.

``one_call(caller, state, phase) -> Call | [Call, ...]``
    What a caller does when its draw lands on the kind: through
    ``caller.api`` (the planted facade where a control runs), every draw
    from ``caller.rng``.  ``caller.new_call(kind, keynums, versions,
    phase)`` stamps the send time; the generator stamps the completion
    of a call the kind left open.  It fills ``errors`` with one entry per
    item of ``keynums`` (``None`` or a string; any other length is
    refused) and may keep what came back in ``values``.  It returns at
    least one call of its own, and may return built-in calls beside it
    (``caller.builtin("insert", phase)`` issues one as the generator
    would): those fall under the history, read-back and the disk judge by
    the rules that exist.  A call of any other kind than its own or a
    built-in is refused.

    What a draw counts: the items of the kind's OWN calls, and nothing
    else, go into ``attempted``, ``failed`` and ``committed_ops_per_s``.
    The built-in calls beside them are marked (``Call.beside``), judged as
    any other and not counted again: a certificate that is signed and
    then stored is one operation, not 1 + ``batch``.  Where the stored
    half failing should fail the operation, the kind says so in its own
    call's ``errors``.

``judge(calls, state, ctx) -> {number: value}``
    After the window, from the kind's own calls (every phase of that
    window) and what ``prepare`` returned: every number ``LIMITS`` names.
    It judges with ``benchmarks/reference.py`` or a reference file of its
    own beside it; it imports nothing of the program.

``PLANTS`` (optional)
    ``{name: class}`` in ``plants._Planted``'s shape (``(api, every)``,
    everything it does not break passed through), named in the mix's
    ``controls`` and accepted by ``--plant`` and ``--control-runs``.  A
    kind whose cell is entered without a control that comes out incorrect
    is not judged.
"""

from __future__ import annotations

import importlib
import os
import re

from benchmarks import generator, judge, plants
from benchmarks.harness import BenchFailure

HERE = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")  # a module's name
ALLOWED = {("<=", 0), (">=", 1)}


class Kind:
    """One loaded kind: the module's functions under checked names."""

    def __init__(self, name: str, module):
        self.name, self.module = name, module
        self.limits = [tuple(lim) for lim in getattr(module, "LIMITS", ())]
        self.plants = dict(getattr(module, "PLANTS", {}))
        self.state = None
        for fn in ("one_call", "judge"):
            if not callable(getattr(module, fn, None)):
                raise BenchFailure(f"kind '{name}' has no {fn}()")
        if not self.limits:
            raise BenchFailure(
                f"kind '{name}' declares no LIMITS: nothing of it would be "
                "judged")

    def prepare(self, ctx: dict) -> None:
        fn = getattr(self.module, "prepare", None)
        if fn is None:
            return
        try:
            self.state = fn(ctx)
        except Exception as e:
            raise BenchFailure(f"kind '{self.name}': prepare raised {e!r}")

    def one_call(self, caller, phase: str) -> list:
        try:
            got = self.module.one_call(caller, self.state, phase)
        except Exception as e:  # the caller's thread hands it to the harness
            raise RuntimeError(f"kind '{self.name}': one_call raised {e!r}")
        calls = [got] if isinstance(got, generator.Call) else list(got)
        for c in calls:
            if c.kind != self.name and c.kind not in generator.KINDS:
                raise RuntimeError(
                    f"kind '{self.name}' returned a call of kind '{c.kind}'")
            if c.kind != self.name:
                c.beside = self.name  # judged, and counted with the own call
            elif len(c.errors) != len(c.keynums):
                raise RuntimeError(
                    f"kind '{self.name}' returned a call with "
                    f"{len(c.errors)} errors for {len(c.keynums)} items: one "
                    "entry an item, None where it was acknowledged")
        if not any(c.kind == self.name for c in calls):
            raise RuntimeError(
                f"kind '{self.name}' returned no call of its own: the draw "
                "would count nothing")
        return calls

    def judge(self, calls: list, ctx: dict) -> dict:
        own = [c for c in calls if c.kind == self.name]
        try:
            numbers = dict(self.module.judge(own, self.state, ctx))
        except Exception as e:
            raise BenchFailure(f"kind '{self.name}': judge raised {e!r}")
        missing = [n for n, _op, _lim in self.limits if n not in numbers]
        if missing:
            raise BenchFailure(f"kind '{self.name}': judge returned no "
                               f"{missing} (LIMITS names them)")
        return numbers


def _check_limits(kind: Kind, taken: set) -> None:
    for lim in kind.limits:
        if len(lim) != 3:
            raise BenchFailure(f"kind '{kind.name}': limit {lim!r} is not "
                               "(name, op, limit)")
        name, op, limit = lim
        if type(limit) is not int or (op, limit) not in ALLOWED:
            raise BenchFailure(
                f"kind '{kind.name}': the limit of '{name}' is {op} {limit!r}; "
                "a number that decides 'correct' is exact: only <= 0 or >= 1")
        if name in taken:
            raise BenchFailure(f"kind '{kind.name}': the number '{name}' is "
                               "taken by a built-in number or another kind")
        taken.add(name)


def _check_plants(kind: Kind, taken: set) -> None:
    for name, cls in kind.plants.items():
        if name in taken:
            raise BenchFailure(f"kind '{kind.name}': the plant '{name}' is "
                               "taken by a built-in plant or another kind")
        if not callable(cls):
            raise BenchFailure(f"kind '{kind.name}': the plant '{name}' is "
                               "not a class taking (api, every)")
        taken.add(name)


def load(names) -> dict:
    """``{name: Kind}`` for the shares of a mix that are no built-in, in
    the mix's order.  Anything wrong is a ``BenchFailure`` that names the
    kind."""
    numbers = {n for n, _op, _lim in judge.LIMITS}
    planted = {"dead_child", *plants.PLANTS, *plants.SIDECAR_PLANTS}
    out: dict = {}
    for name in names:
        if name in generator.KINDS:
            continue
        if not _NAME.match(name) or not os.path.exists(
                os.path.join(HERE, name + ".py")):
            raise BenchFailure(
                f"unknown operation kind '{name}': not one of "
                f"{list(generator.KINDS)} and no benchmarks/kinds/{name}.py")
        importlib.invalidate_caches()
        try:
            module = importlib.import_module("benchmarks.kinds." + name)
        except Exception as e:
            raise BenchFailure(f"kind '{name}' does not import: {e!r}")
        kind = Kind(name, module)
        _check_limits(kind, numbers)
        _check_plants(kind, planted)
        out[name] = kind
    return out
