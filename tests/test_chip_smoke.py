"""chip_smoke.py's phase functions at toy sizes on the CPU (interpret-mode
kernels), and the script's refusal to run without a TPU.

The script itself has no CPU mode; what tier-1 can hold is that its phase
bodies — the same code the chip runs at full width — stay runnable, and
that `python chip_smoke.py` fails in the device phase, before any model
is built, wherever jax finds no accelerator."""

import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY_LM = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
              vocab_chunk=32)


@pytest.fixture(scope="module")
def trained():
    with pytest.MonkeyPatch.context() as mp:
        # DL4JTPU_FLASH=1 routes mha() to the flash kernel off-TPU
        # (interpret mode) — on the chip the phase asserts the DEFAULT
        # dispatch picks it
        mp.setenv("DL4JTPU_FLASH", "1")
        return chip_smoke.phase_train(TOY_LM, batch=2, seq=128,
                                      expect_bf16=False)


def test_train_phase_loss_falls_through_flash(trained):
    assert len(trained["losses"]) == 10
    assert trained["losses"][-1] < trained["losses"][0]


def test_flash_spy_sees_a_dense_dispatch():
    """The train phase's flash assertion rests on this: when mha() takes
    the dense O(T^2) path (the CPU default), the spy records a refusal —
    so the phase FAILS rather than passing on dense attention."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.attention import mha

    q = jnp.zeros((1, 128, 2, 16), jnp.float32)
    with chip_smoke._FlashSpy() as spy:
        mha(q, q, q, causal=True)
    assert spy.calls == [((1, 128, 2, 16), False)]


def test_serve_phase_answers_http_generate(trained):
    out = chip_smoke.phase_serve(
        trained["model"], prompt_lens=(5, 9, 12, 20), max_new=4,
        page_size=8, prefill_quantum=8)
    assert out["requests"] == 4 and len(out["buckets"]) >= 2
    assert out["paged_impl"] == ["xla"]        # what select_impl() says on CPU


def test_kernels_phase_interpret_mode():
    chip_smoke.phase_kernels(
        flash_shape=(1, 128, 2, 32),
        paged=dict(slots=6, heads=2, head_dim=32, page_size=8,
                   pages_per_seq=4, num_pages=16),
        dequant_kn=(256, 256), dequant_ms=(1, 8), interpret=True)


def test_multichip_train_phase_on_virtual_devices(monkeypatch):
    """The data-parallel branch (2 virtual devices here, 4 chips in the
    script): batch shards on distinct devices, the flash kernel wrapped
    per shard, and the first-step loss equal to the one-device loss of
    the same batch."""
    monkeypatch.setenv("DL4JTPU_FLASH", "1")
    ref = chip_smoke.one_chip_first_loss(TOY_LM, batch=4, seq=128, parts=2)
    chip_smoke.phase_train(TOY_LM, batch=4, seq=128, expect_bf16=False,
                           data_parallel=2, reference_first_loss=ref)


def test_script_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    # failed in the device phase: no phase ran, no result line
    assert "train:" not in proc.stdout
    assert '"ok"' not in proc.stdout


def test_result_line_has_exactly_the_contract_keys():
    """The driver parses the last stdout line: `ok` and `device` only,
    `device` = platform/kind (text) and count (a whole number)."""
    import json

    import numpy as np

    doc = json.loads(chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": np.int64(1)}))
    assert doc == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(doc) == ["ok", "device"]
    assert type(doc["device"]["count"]) is int
