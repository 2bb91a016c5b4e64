"""Cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds, and
one run of such a cell with the chip check skipped."""

import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from bench import harness  # noqa: E402
from bench import run as brun  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}

TINY_MODEL = {
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2, "vocab_size": 1024,
}
TINY_SERVING = {"kv_page_tokens": 8, "use_pallas": False, "pool_slots": 256,
                "decode_rows": 4}
TINY_NEW = {"median": 12, "sigma": 0.8, "min": 4, "max": 24, "step": 1}
TINY_TRAFFIC = {
    "prefix_fetch": {"rate_per_s": 6.0, "shared_prefixes": {"count": 3, "tokens": 32},
                     "tail_tokens": {"median": 12, "sigma": 0.8, "min": 8, "max": 32, "step": 8},
                     "new_tokens": TINY_NEW},
    "unshared": {"rate_per_s": 6.0,
                 "tail_tokens": {"median": 24, "sigma": 0.5, "min": 16, "max": 48, "step": 8},
                 "new_tokens": TINY_NEW},
}
TINY_CKPT = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
             "vocab_size": 512, "checkpointer": {"page_size": 4096, "keep_last": 3}}


def tiny_cell(name: str) -> harness.Cell:
    """The named cell of ``BENCHMARK.json`` at a size for the CPU."""
    cell = harness.Cell(harness.load_json(ROOT / "BENCHMARK.json"), name)
    cfg = copy.deepcopy(cell.config)
    if cfg["system"] == "blob_kv_serve":
        cfg.update(TINY_MODEL)
        cfg["serving"].update(TINY_SERVING)
        cell.traffic = {**cell.traffic, **TINY_TRAFFIC[cell.workload["traffic"]]}
    else:
        cfg.update(TINY_CKPT)
    cell.config = cfg
    return cell


def run_tiny(name: str, seed: int = 2**33 + 5, seconds: float = 3.0, check: str = "cell",
             patch=None) -> dict:
    """One run of the named cell at the CPU size. ``patch(sut)`` runs once
    set-up has ended, before the window: it breaks the timed path."""
    cell = tiny_cell(name)
    system = cell.system()
    setup = system.setup

    def patched(*args, **kwargs):
        sut = setup(*args, **kwargs)
        if patch is not None:
            patch(sut)
        return sut

    system.setup = patched
    try:
        args = brun.parse(["--workload", name, "--seed", str(seed), "--seconds", str(seconds),
                           "--trace", "0", "--check", check])
        return brun.run_cell(args, jax.devices()[:1], PEAKS, time.perf_counter(), cell=cell)
    finally:
        system.setup = setup
