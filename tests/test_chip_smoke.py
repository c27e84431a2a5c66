"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, its
four-chip agreement check tells a trained state from an untrained one, and
its four-chip path runs end to end at the CPU-sized model on 4 host devices
(the same init, placement, eval and donating step the chip runs, through
the jnp lowering instead of the Pallas kernels)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _env(**extra):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_exits_nonzero_without_a_tpu():
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=_env(), capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "not a TPU" in res.stderr


def _states(seed, scale):
    rng = np.random.default_rng(seed)
    tree = lambda: {"x": {"a": rng.normal(size=(2, 5)).astype(np.float32),
                          "b": rng.normal(size=(2, 3)).astype(np.float32)},
                    "y": {"w": rng.normal(size=(2, 4)).astype(np.float32)},
                    "u": {"w": rng.normal(size=(2, 4)).astype(np.float32)}}
    start, step = tree(), tree()
    end = {s: {k: start[s][k] + scale * step[s][k] for k in start[s]}
           for s in start}
    return start, end


@pytest.mark.parametrize("noise,agrees", [(0.0, True), (1e-4, True),
                                          (0.5, False)])
def test_drift_separates_trained_from_untrained(noise, agrees):
    start, ref_end = _states(0, 1e-2)
    _, jitter = _states(1, 1.0)
    end = {s: {k: ref_end[s][k] + noise * 1e-2 * jitter[s][k]
               for k in ref_end[s]} for s in ref_end}
    losses, ref = [5.0, 4.9, 4.8], [5.0, 4.9, 4.8]
    d = chip_smoke.drift(start, end, start, ref_end)
    assert chip_smoke.agree(d, chip_smoke.loss_drift(losses, ref)) == agrees
    planted = chip_smoke.drift(start, start, start, ref_end)
    for sec in ("x", "y", "u"):
        assert planted[sec]["rel"] == pytest.approx(1.0)
        assert planted[sec]["worst_leaf"] == pytest.approx(1.0)
    assert chip_smoke.loss_drift(losses[:1] * 3, ref) == 1.0
    assert not chip_smoke.agree(planted, 1.0)


_FOUR = """
import sys
sys.path.insert(0, {root!r})
import jax
import chip_smoke
chip_smoke.four_chips(jax.devices(), reduced=True)
print("FOUR_OK")
"""


@pytest.mark.timeout(600)
@pytest.mark.mesh_subprocess
def test_four_chip_path_on_host_devices():
    env = _env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", _FOUR.format(root=ROOT)],
                         env=env, capture_output=True, text=True,
                         timeout=550)
    assert res.returncode == 0, (res.stdout[-3000:], res.stderr[-3000:])
    assert "FOUR_OK" in res.stdout
    rows = dict(line.split(": ", 1) for line in res.stdout.splitlines()
                if line.startswith(("sharded_layout", "sharded_vs")))
    layout = json.loads(rows["sharded_layout"])
    assert layout["shard_devices"] == [0, 1, 2, 3]
    cmp = json.loads(rows["sharded_vs_one_chip"])
    for sec in ("x", "y", "u"):
        assert cmp["state_drift"][sec]["rel"] <= chip_smoke.STATE_RTOL
    assert cmp["loss_drift"] <= chip_smoke.LOSS_RTOL
