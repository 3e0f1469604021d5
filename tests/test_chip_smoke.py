"""``chip_smoke.py`` rehearsed on the CPU: green when everything holds,
loud when any of the quiet retreats it exists to catch happens.

Each case runs the script in a subprocess through its own entry point,
at a tiny size (``--rehearse``: the same flow, CPU backend, device
counters printed but not required — calibration pins host there).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
CHIP_VERDICT_KEYS = {"ok", "device"}


def run_smoke(*argv, cwd=ROOT, script=SMOKE, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # the script finds its own checkout
    p = subprocess.run(
        [sys.executable, script, *argv], cwd=cwd, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=timeout,
    )
    records = []
    for line in p.stdout.splitlines():
        try:
            records.append(json.loads(line))
        except ValueError:
            pass
    return p, records


def is_chip_verdict(record: dict) -> bool:
    """The one line the driver accepts: ok, on a TPU, nothing else."""
    return (
        set(record) == CHIP_VERDICT_KEYS
        and record["ok"] is True
        and record["device"].get("platform") == "tpu"
    )


def by_phase(records: list, phase: str) -> dict:
    return next(r for r in records if r.get("phase") == phase)


def _run_dirs() -> set:
    try:
        return set(os.listdir(os.path.join(ROOT, ".chip_smoke")))
    except OSError:
        return set()


def test_rehearsal_is_green_and_says_it_is_a_rehearsal():
    before = _run_dirs()
    p, records = run_smoke(
        "--rehearse", "--phases", "tiers,cluster",
        "--keys", "16", "--batch", "8",
    )
    assert p.returncode == 0, p.stderr[-3000:]
    last = records[-1]
    assert last["ok"] is True and last["rehearsal"] is True
    assert not is_chip_verdict(last)
    assert last["device"]["platform"] == "cpu"
    # The parent stayed off JAX: it starts the children that need the chip.
    assert by_phase(records, "end")["parent_imported_jax"] is False
    # Built from what git commits: all three native tiers loaded.
    assert all(by_phase(records, "native_tiers")["loaded"].values())
    # Every process of the cluster was pinned to the CPU backend here
    # (on the chip the sidecar alone is not).
    procs = by_phase(records, "children")["processes"]
    assert len(procs) == 9
    assert {c["JAX_PLATFORMS"] for c in procs} == {"cpu"}
    # All values read back equal; the guarantees held.
    assert by_phase(records, "load")["ops"] == 16
    back = by_phase(records, "readback")
    assert back["ops"] == 16 and back["wrong_values"] == 0
    singles = by_phase(records, "singles")
    assert singles["overwrite_visible"]
    assert singles["writeonce_rewrite_refused"]
    # Tenants did go through the sidecar, and nothing retreated.
    counters = by_phase(records, "counters")
    assert counters["tenants"]["verify.remote"] > 0
    assert counters["tenants"]["sign.remote"] > 0
    assert not any(counters["must_be_zero"].values())
    # The run's directory under the checkout is gone.
    assert _run_dirs() <= before


@pytest.mark.parametrize(
    "inject,expect",
    [
        # The sidecar process is killed after the load: the launcher
        # keeps the fleet up (right in production), the smoke must not.
        ("kill-sidecar", "sidecar"),
        # The sidecar's socket vanishes: every tenant quietly runs its
        # own host crypto and every write still commits.
        ("unlink-socket", "remote_fallback"),
    ],
)
def test_quiet_retreat_is_a_loud_failure(inject, expect):
    p, records = run_smoke(
        "--rehearse", "--phases", "cluster", "--keys", "8", "--batch", "8",
        "--inject", inject,
    )
    assert p.returncode != 0
    assert not any("ok" in r for r in records)
    assert "chip_smoke: FAIL" in p.stderr and expect in p.stderr
    # The store itself stayed correct — that is what makes it quiet.
    assert by_phase(records, "readback")["wrong_values"] == 0


def test_without_a_chip_there_is_no_verdict():
    """No ``--rehearse`` and no accelerator (JAX is held to the CPU
    here): non-zero, and no result line."""
    p, records = run_smoke("--phases", "kernels", timeout=120)
    assert p.returncode != 0
    assert by_phase(records, "device")["platform"] == "cpu"
    assert not any("ok" in r for r in records)
    assert "found no TPU" in p.stderr


def test_the_script_alone_fails(tmp_path):
    """In a directory that holds ``chip_smoke.py`` and nothing else of
    the repo it has no program to drive."""
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p, records = run_smoke(cwd=tmp_path, script=str(alone), timeout=120)
    assert p.returncode != 0
    assert not any("ok" in r for r in records)
