"""A run with its timed path broken underneath must come out not correct,
once for each fault a cell can have, and so must the control. The runs skip
the look for a chip and are cut to a size the CPU holds (``cells.py``)."""

import dataclasses

import numpy as np
import pytest

from cells import run_tiny


def _broken(result: dict) -> bool:
    return result["correct"] is False


# ------------------------------- KV serving -------------------------------
@pytest.mark.parametrize("cell", ["kv_prefix_fetch", "kv_unshared"])
def test_sound_run_is_correct(cell):
    out = run_tiny(cell)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("cell", ["kv_prefix_fetch", "kv_unshared"])
def test_altered_token_is_caught(cell):
    def patch(sut):
        eng, sample = sut.engine, sut.engine._sample
        calls = {"n": 0}

        def altered(logits, temperature):
            calls["n"] += 1
            tok = sample(logits, temperature)
            return (tok + 1) % sut.mcfg.vocab_size if calls["n"] % 7 == 0 else tok

        eng._sample = altered

    out = run_tiny(cell, patch=patch)
    assert _broken(out)
    assert out["checks"]["served_logit_gap"]["value"] > out["checks"]["served_logit_gap"]["limit"]


@pytest.mark.parametrize("cell", ["kv_prefix_fetch", "kv_unshared"])
def test_decode_step_that_keeps_its_pool_is_caught(cell):
    def patch(sut):
        eng, decode = sut.engine, sut.engine._jit_decode

        def unchanged(params, pool_k, pool_v, *rest):
            logits, _, _, page_pos = decode(params, pool_k, pool_v, *rest)
            return logits, pool_k, pool_v, page_pos

        eng._jit_decode = unchanged

    out = run_tiny(cell, patch=patch)
    assert _broken(out)


def test_altered_fetched_page_is_caught():
    def patch(sut):
        session = sut.client.session
        read = session.read_pages

        def altered(*args, **kwargs):
            pages = [np.array(p, copy=True) for p in read(*args, **kwargs)]
            pages[0][100] ^= 1
            return pages

        session.read_pages = altered

    out = run_tiny("kv_prefix_fetch", patch=patch)
    assert _broken(out)
    assert out["checks"]["fetched_pages_differing"]["value"] > 0


@pytest.mark.parametrize("cell", ["kv_prefix_fetch", "kv_unshared"])
def test_fp8_control_fails_the_limit(cell):
    out = run_tiny(cell, check="control")
    assert _broken(out)
    gap = out["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert out["checks"]["fetched_pages_differing"]["value"] == 0


# ------------------------------- checkpoints -------------------------------
def test_sound_checkpoint_cycle_is_correct():
    out = run_tiny("ckpt_cycle")
    assert out["correct"] is True, out["checks"]


def test_restore_of_the_previous_checkpoint_is_caught():
    def patch(sut):
        restore = sut.ckpt.restore

        def stale(step=None, shardings=None):
            return restore(step=sut.ckpt.checkpoints[-2].step, shardings=shardings)

        sut.ckpt.restore = stale

    assert _broken(run_tiny("ckpt_cycle", patch=patch))


def test_altered_restored_byte_is_caught():
    def patch(sut):
        read = sut.ckpt.handle.read

        def altered(*args, **kwargs):
            res = read(*args, **kwargs)
            data = np.array(res.data, copy=True)
            data.reshape(-1).view(np.uint8)[0] ^= 1
            return dataclasses.replace(res, data=data)

        sut.ckpt.handle.read = altered

    assert _broken(run_tiny("ckpt_cycle", patch=patch))


def test_bf16_control_of_the_checkpoint_fails():
    out = run_tiny("ckpt_cycle", check="control")
    assert _broken(out)
    assert out["checks"]["restored_elements_differing"]["value"] > 0
