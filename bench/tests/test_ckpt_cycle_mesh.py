"""The 2x2 checkpoint cycle (``ckpt_cycle_2x2``) at a CPU size: a sound run
comes out correct; the control, a restore that swaps two devices' shards
and a restore of the previous checkpoint each come out not correct.

The runs need four devices, so they run in a child process whose JAX has
four CPU devices (``--xla_force_host_platform_device_count=4``); one child
runs every case and each case is reported as a test of its own.

    python bench/tests/test_ckpt_cycle_mesh.py   # the child: one JSON line
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import traceback
from pathlib import Path

import pytest

CELL = "ckpt_cycle_2x2"
ROOT = Path(__file__).resolve().parents[2]


def _swap_two_shards(sut) -> None:
    """Each restore hands back its first sharded leaf with the shards of
    two devices exchanged."""
    import jax

    restore = sut.ckpt.restore

    def swapped(step=None, shardings=None):
        leaves, treedef = jax.tree.flatten(restore(step=step, shardings=shardings))
        i = next(k for k, x in enumerate(leaves) if len({s.index for s in x.addressable_shards}) > 1)
        x = leaves[i]
        shards = [(s.device, s.data) for s in x.addressable_shards]
        (d0, a0), (d1, a1) = shards[:2]
        arrays = [jax.device_put(a1, d0), jax.device_put(a0, d1)] + [a for _, a in shards[2:]]
        leaves[i] = jax.make_array_from_single_device_arrays(x.shape, x.sharding, arrays)
        return jax.tree.unflatten(treedef, leaves)

    sut.ckpt.restore = swapped


def _stale(sut) -> None:
    restore = sut.ckpt.restore

    def stale(step=None, shardings=None):
        return restore(step=sut.ckpt.checkpoints[-2].step, shardings=shardings)

    sut.ckpt.restore = stale


def _layer_metrics() -> dict:
    """A window driven with the cell's spans in place, as a traced run has
    them (the profiler aside), and every per-layer metric of the cell read
    from it."""
    from bench import harness
    from cells import PEAKS, tiny_cell

    cell = tiny_cell(CELL)
    system = cell.system()
    run = harness.Run(cell, PEAKS)
    run.sut = system.setup(cell, 2**33 + 7, 2.0)
    system.instrument(run.sut, run.spans)
    run.window = system.drive(run.sut, 2.0, run.spans)
    return {m["name"]: harness.metric_reader(m["name"])(run) for m in cell.per_layer}


def _child() -> None:
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from cells import run_tiny

    try:
        layers = _layer_metrics()
    except Exception:
        layers = {"error": traceback.format_exc()}

    runs = {"sound": {}, "control": {"check": "control"},
            "swapped_shards": {"patch": _swap_two_shards}, "stale_restore": {"patch": _stale}}
    out = {}
    for name, kwargs in runs.items():
        try:
            result = run_tiny(CELL, **kwargs)
            out[name] = {"correct": result["correct"], "checks": result["checks"],
                         "failed": result["failed"], "device_count": result["device"]["count"]}
        except Exception:
            out[name] = {"error": traceback.format_exc()}
    out["layers"] = layers
    print(json.dumps(out))


@pytest.fixture(scope="module")
def outcomes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__], env=env, capture_output=True, text=True,
                          timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(outcomes):
    out = outcomes["sound"]
    assert "error" not in out, out.get("error")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["device_count"] == 4


@pytest.mark.parametrize("run", ["control", "swapped_shards", "stale_restore"])
def test_broken_run_is_not_correct(outcomes, run):
    out = outcomes[run]
    assert "error" not in out, out.get("error")
    assert out["correct"] is False
    assert out["checks"]["restored_elements_differing"]["value"] > 0


def test_every_layer_metric_reads_a_number(outcomes):
    layers = outcomes["layers"]
    assert "error" not in layers, layers.get("error")
    assert set(layers) == {"ckpt_save_self_s", "ckpt_restore_self_s", "ckpt_shard_save_s",
                           "ckpt_shard_restore_s", "ckpt_host_wait_s"}
    assert all(isinstance(v, float) and v >= 0 for v in layers.values()), layers


if __name__ == "__main__":
    _child()
