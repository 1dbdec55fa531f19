"""`held_gmm`, the grouped product that fetches only the experts with rows,
against ``lax.ragged_dot`` — interpreted on the CPU — on the rows inside
the groups, for f32 and bf16 outputs; `moe_ffn` with the kernel in its
static pass against the same layer through ``lax.ragged_dot``; and the
engine's device count of the experts fetched against a count made on the
host from the routing of every decode step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.ops import latent, moe
from deeplearning4j_tpu.ops.held_expert_matmul import (
    expert_metadata, held_gmm,
)

#: rows per group: (name, sizes, rows m); the rows past the groups are
#: m - sum(sizes)
CASES = [
    ("no_held_rows", [0, 0, 0, 0, 0, 0], 24),
    ("one_group", [0, 0, 24, 0, 0, 0], 24),
    ("every_group", [3, 5, 7, 2, 4, 3], 24),
    ("straddles_blocks", [5, 0, 14, 0, 1, 0], 24),
    ("rows_past_groups", [0, 2, 0, 1, 0, 1], 24),
    ("n_active_is_rows", [1] * 16 + [0] * 8, 16),
]


def _operands(sizes, m, k=128, n=256):
    kx, kw = jax.random.split(jax.random.key(len(sizes) + m + sum(sizes)))
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (len(sizes), k, n), jnp.float32)
         / np.sqrt(k)).astype(jnp.bfloat16)
    return x, w, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("out", [jnp.float32, None], ids=["f32", "bf16"])
@pytest.mark.parametrize("name,sizes,m", CASES, ids=[c[0] for c in CASES])
def test_held_gmm_is_ragged_dot_on_the_rows_inside_the_groups(name, sizes,
                                                              m, out):
    x, w, s = _operands(sizes, m)
    got = held_gmm(x, w, s, out, interpret=True)
    want = lax.ragged_dot(x, w, s, preferred_element_type=out)
    assert got.shape == want.shape and got.dtype == want.dtype
    inside = int(sum(sizes))
    tol = 1e-5 if out is not None else 1e-2
    np.testing.assert_allclose(np.asarray(got[:inside], np.float32),
                               np.asarray(want[:inside], np.float32),
                               rtol=tol, atol=tol)
    # rows past the groups: nothing is asked of them; the kernel leaves 0
    assert not np.any(np.asarray(got[inside:], np.float32))


@pytest.mark.parametrize("name,sizes,m", CASES, ids=[c[0] for c in CASES])
def test_metadata_lists_the_nonempty_groups_first(name, sizes, m):
    e_max = min(len(sizes), m)
    ids, n_active, starts, counts = (
        np.asarray(a) for a in expert_metadata(jnp.asarray(sizes), e_max))
    nonempty = [g for g, c in enumerate(sizes) if c]
    assert ids.shape == (e_max,) and int(n_active[0]) == len(nonempty)
    assert ids[:len(nonempty)].tolist() == nonempty
    # the padding repeats the last id, so a padded grid step names the
    # block the step before it named and fetches nothing
    assert set(ids[len(nonempty):].tolist()) <= {ids[max(len(nonempty) - 1,
                                                         0)]}
    first = np.cumsum(sizes) - np.asarray(sizes)
    assert starts[:len(nonempty)].tolist() == first[nonempty].tolist()
    assert counts.tolist() == ([sizes[g] for g in nonempty]
                               + [0] * (e_max - len(nonempty)))


def _moe_params(key, dtype, d=32, f=16, n=8):
    ks = jax.random.split(key, 10)
    mat = lambda k, *s: (jax.random.normal(k, s) / np.sqrt(s[-2])).astype(
        dtype)
    poly = lambda k, *lead: {
        "poly_w": jnp.full(lead + (3,), 1 / 3) + 0.1 * jax.random.normal(
            k, lead + (3,)), "poly_b": 0.05 * jax.random.normal(k, lead)}
    return {"router": jax.random.normal(ks[0], (d, 16)),
            "router_bias": 0.1 * jax.random.normal(ks[7], (16,)),
            "experts": {"Wg": mat(ks[1], n, d, f), "Wu": mat(ks[2], n, d, f),
                        "Wd": mat(ks[3], n, f, d), **poly(ks[8], n)},
            "shared": {"Wg": mat(ks[4], d, f), "Wu": mat(ks[5], d, f),
                       "Wd": mat(ks[6], f, d), **poly(ks[9])}}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("act", [None, moe.PolyNorm(0.5, 0.5, 1e-5)],
                         ids=["silu", "polynorm"])
def test_moe_ffn_static_pass_through_the_kernel_is_the_ragged_dot_pass(
        monkeypatch, act, dtype):
    """A decode step's rows (12 rows x top-2 = 24 of the row tile's 2048)
    on a chip holding 8 of 16 experts: the kernel's pass against XLA's."""
    lp = _moe_params(jax.random.key(0), dtype)
    h = jax.random.normal(jax.random.key(1), (12, 32)).astype(dtype)
    kw = dict(first=4, top_k=2, scale=2.0, act=act,
              row_tile=latent.MOE_ROW_TILE)
    want, c_want, f_want = moe.moe_ffn(h, lp, **kw)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    got, c_got, f_got = moe.moe_ffn(h, lp, **kw)
    assert got.dtype == want.dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert c_got.tolist() == c_want.tolist()
    # the held experts with rows, counted from the routing on the host
    s = jax.nn.sigmoid(np.asarray(h, np.float32) @ np.asarray(lp["router"]))
    _, ids = lax.top_k(s + lp["router_bias"], 2)
    held = {int(i) - 4 for i in np.asarray(ids).ravel() if 4 <= i < 12}
    assert int(f_got) == int(f_want) == len(held) > 0


def test_the_tiled_pass_reports_no_fetch_count():
    lp = _moe_params(jax.random.key(2), jnp.float32)
    h = jax.random.normal(jax.random.key(3), (20, 32))
    _, _, fetched = moe.moe_ffn(h, lp, first=4, top_k=2, scale=2.0,
                                row_tile=8)
    assert fetched is None


def test_engine_counts_the_experts_its_decode_steps_fetched(monkeypatch):
    """The device count against the host: every expert layer of every
    decode step (3 slots x top-2 = 6 rows, 4 held experts) hands its rows'
    routing to the host, which counts the held experts with a row; the
    engine's `experts_fetched` and the registry's counter must equal the
    sum, and `expert_layer_steps` the number of such layer-steps."""
    from test_motif3_serving import ENGINE, _model, _prompt

    from deeplearning4j_tpu.observe.metrics import registry
    from deeplearning4j_tpu.serving.generation import (
        MOE_FETCH_FAMILIES, GenerationConfig, GenerationEngine,
    )

    seen = []
    real = latent.moe_ffn

    def recorded(h, lp, *, first, top_k, **kw):
        ids, _ = moe.route(h, lp["router"], lp["router_bias"], top_k=top_k,
                           scale=kw["scale"])
        n_held = lp["experts"]["Wg"].shape[0]
        jax.debug.callback(
            lambda ids: seen.append(
                (ids.shape[0], len({int(i) - first for i in ids.ravel()
                                    if first <= i < first + n_held}))),
            ids)
        return real(h, lp, first=first, top_k=top_k, **kw)

    def totals():
        reg.collect()
        snaps = [reg.get(f).snapshot() for f in MOE_FETCH_FAMILIES]
        return [s.get("value", sum(s.get("series", {}).values()))
                for s in snaps]

    monkeypatch.setattr(latent, "moe_ffn", recorded)
    reg = registry()
    before = totals()
    eng = GenerationEngine(model=_model(), config=GenerationConfig(**ENGINE))
    req = eng.submit(_prompt(20), 6)
    eng.start()
    try:
        req.result(timeout=300)
    finally:
        eng.stop()
    jax.effects_barrier()
    st = eng.stats()["moe"]
    after = totals()
    steps = [n for rows, n in seen if rows == ENGINE["slots"]]
    assert len(steps) >= 4 * 5                  # 4 expert layers, 5 steps
    assert st["expert_layer_steps"] == len(steps)
    assert st["experts_fetched"] == sum(steps) > 0
    assert [a - b for a, b in zip(after, before)] == [sum(steps),
                                                      len(steps)]
