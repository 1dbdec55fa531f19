"""Family `phi4flash`: the SambaY decoder-hybrid-decoder of
Phi-4-mini-flash-reasoning — Mamba-1 and sliding-window layers in a
self-decoder, one full-attention layer whose keys and values are the only
full-context ones the model keeps, then gated memory units and
cross-attention layers that read that memory and those keys and values —
built with the system's config DSL, its plain float32 reference, and its
analytic operation and byte counts.

The equations (x: one token's residual; every norm a LayerNorm with a bias,
no projection bias; layer l of `layer_kinds`):

    block    x += mixer_l(LN1 x);  x += W2 (silu(g) * u), [g; u] = LN2(x) W1
             after the last: LN_f, and logits = LN_f(h) E^T (tied head)
    mamba    [u; z] = h W_in;  u' = silu(causal depthwise conv_4(u) + b)
             [d; B; C] = u' W_x;  dt = softplus(d W_dt + b_dt);  A = -exp(A_log)
             s_t = exp(dt_t A) * s_{t-1} + (dt_t u'_t) B_t^T;  y_t = s_t C_t + D u'_t
             out = (y * silu(z)) W_out;  the tap layer's y * silu(z) is M
    attn     ("swa": keys within the last `sliding_window` positions; "full":
             all before;  "cross": the full layer's K and V, its own Q only)
             q pairs (Q_i0, Q_i1), key pairs (K_j0, K_j1), values V_j, j = i // 2
             O_i = softmax(Q_i0 K_j0^T / 8) V_j - lam softmax(Q_i1 K_j1^T / 8) V_j
             O_i = RMSNorm(O_i) * g * (1 - lam_init);  out = [O_i] W_o
             lam = exp(lq1.lk1) - exp(lq2.lk2) + lam_init, lam_init = 0.8 - 0.6 exp(-0.3 l)
    gmu      out = (M * silu(h W_1)) W_2

The reference below is those lines in `jax.numpy`, float32, "highest" matmul
precision, nothing imported from the package: no cache, no pages, no slots,
no batching; the recurrence is a `lax.scan` over positions, attention runs in
blocks of query rows and the head in blocks of the vocabulary, and every
matrix is upcast where it is used, so that it fits beside the weights at the
timed sizes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

EMBED, DECODER = "embed", "decoder"
QUERY_BLOCK = 128
#: the largest block of vocabulary rows the reference's head takes at once
VOCAB_BLOCK = 32768
#: the embedding's rows are drawn at this standard deviation.  The head is
#: tied, so a position's logit of its OWN token is LN_f(h) . E_tok, which
#: carries the embedding's share of the residual times the width: at N(0, 1)
#: rows that share (1 of the ~20 the 64 sublayers add at the DSL's unit-gain
#: matrices) makes every position's own token the arg-max (546 against a
#: maximum of ~223 over 200,064 others at hidden 2,560), the model echoes its
#: input and a comparison of arg-max logits sees nothing the layers do (a
#: small twin on the CPU echoed 91 % of its tokens).  At 0.1 the own-token
#: term is a quarter of the vocabulary's maximum and the layers decide
EMBED_STD = 0.1
#: leaves kept in float32 in the served tree: the recurrence's constants and
#: the differential attention's lambda vectors (1 MB in all); every matrix
#: and norm is bfloat16
F32_LEAVES = frozenset(("A_log", "D", "b_dt", "conv_w", "conv_b",
                        "lq1", "lk1", "lq2", "lk2"))


# -- the configuration as run ------------------------------------------------------

def layer_kinds(cfg: dict) -> list[str]:
    """The mixer of each layer: the self-decoder's first L/2 + 1 layers
    alternate Mamba (every `mb_per_layer`-th, from 0) and sliding-window
    attention, layer L/2 + 1 is the full-attention layer, and the rest
    alternate gated memory units and cross-attention (32 layers: Mamba at
    0, 2, ..., 16, window at 1, ..., 15, full at 17, GMU at 18, ..., 30,
    cross at 19, ..., 31)."""
    n, mb = int(cfg["num_hidden_layers"]), int(cfg["mb_per_layer"])
    half = n // 2
    kinds = ["mamba" if i % mb == 0 else "swa" for i in range(half + 1)]
    kinds.append("full")
    kinds += ["gmu" if i % mb == 0 else "cross" for i in range(half + 2, n)]
    return kinds


def widths(cfg: dict) -> dict:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"d": d, "heads": h, "kv_heads": int(cfg["num_key_value_heads"]),
            "hd": d // h, "ff": int(cfg["intermediate_size"]),
            "window": int(cfg["sliding_window"]),
            "inner": int(cfg["mamba_expand"]) * d,
            "state": int(cfg["mamba_d_state"]),
            "conv": int(cfg["mamba_d_conv"]),
            "rank": int(cfg["mamba_dt_rank"]),
            "vocab": int(cfg["vocab_size"]),
            "eps": float(cfg["layer_norm_eps"])}


# -- the system under test -----------------------------------------------------

def build_model(cfg: dict):
    """An uninitialised `SequentialModel`: [Embedding, HybridDecoder] (the
    head is the embedding's matrix), through the public DSL."""
    from deeplearning4j_tpu.models.sequential import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        Embedding, HybridDecoder, InputType, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.weights import WeightInit

    w = widths(cfg)
    b = (NeuralNetConfiguration.builder()
         .seed(0)
         .weight_init(WeightInit.LECUN_NORMAL)
         .list()
         .layer(Embedding(n_in=w["vocab"], n_out=w["d"], name=EMBED))
         .layer(HybridDecoder(
             name=DECODER, d_model=w["d"], n_heads=w["heads"],
             n_kv_heads=w["kv_heads"], head_dim=w["hd"], d_ff=w["ff"],
             window=w["window"], d_state=w["state"], d_conv=w["conv"],
             expand=int(cfg["mamba_expand"]), dt_rank=w["rank"],
             eps=w["eps"], layer_types=tuple(layer_kinds(cfg)))))
    return SequentialModel(b.set_input_type(InputType.recurrent(1)).build())


def _init_tree(model, key, dtype):
    """The DSL's own draw (`init()` of a twin whose configuration carries
    `key`), the embedding rescaled to `EMBED_STD`, every leaf in `dtype`
    but `F32_LEAVES`."""
    import dataclasses

    twin = type(model)(dataclasses.replace(model.conf, seed=key))
    twin.init()
    params = dict(twin.params)
    e = params[EMBED]["W"]
    params[EMBED] = {"W": e * (EMBED_STD / jnp.std(e))}
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a.astype(
            jnp.float32 if path[-1].key in F32_LEAVES else dtype), params)


def init_on_device(model, *, seed: int, optimizer_state: bool):
    """The weights on the device from `seed` in ONE jitted call, in
    bfloat16: a float32 tree (15.4 GB) cannot exist on the chip beside its
    serving copy, so none is made.  Serving only."""
    if optimizer_state:
        raise ValueError("phi4flash is a serving configuration: it holds "
                         "bfloat16 weights and no optimizer state")
    init = jax.jit(lambda key: _init_tree(model, key, jnp.bfloat16))
    model.params = init(jax.random.key(int(seed)))
    model.net_state, model.opt_state = {}, None
    return model


def abstract_params(cfg: dict):
    """ShapeDtypeStructs of the parameter tree (for offline compiles and
    the parameter count)."""
    model = build_model(cfg)
    return jax.eval_shape(
        lambda key: _init_tree(model, key, jnp.bfloat16), jax.random.key(0))


def param_count(cfg: dict) -> int:
    return sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(abstract_params(cfg)))


# -- the plain reference ---------------------------------------------------------

_f32 = lambda a: jnp.asarray(a, jnp.float32)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(p["gamma"]) + _f32(
        p["beta"])


def _mamba(w, m, h):
    """The Mamba mixer over a whole sequence h: (T, d) -> (out, M)."""
    e, t = w["inner"], h.shape[0]
    uz = h @ _f32(m["W_in"])
    u, z = uz[:, :e], uz[:, e:]
    taps = w["conv"]
    padded = jnp.concatenate([jnp.zeros((taps - 1, e)), u])
    cw = _f32(m["conv_w"])
    u2 = jax.nn.silu(_f32(m["conv_b"]) + sum(
        cw[k] * padded[k:k + t] for k in range(taps)))
    dbc = u2 @ _f32(m["W_x"])
    r, n = w["rank"], w["state"]
    dt = jax.nn.softplus(dbc[:, :r] @ _f32(m["W_dt"]) + _f32(m["b_dt"]))
    b, c = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(_f32(m["A_log"]))

    def step(s, row):
        dt_t, u_t, b_t, c_t = row
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * u_t)[:, None] * b_t[None]
        return s, s @ c_t

    _, y = lax.scan(step, jnp.zeros((e, n)), (dt, u2, b, c))
    mem = (y + _f32(m["D"]) * u2) * jax.nn.silu(z)
    return mem @ _f32(m["W_out"]), mem


def _differential(w, m, layer, q, k, v, window):
    """Differential attention of q (T, heads x hd) over k, v (T, kv_heads x
    hd) of the same positions, causal, keys within `window` positions of
    the query when it is not None."""
    t, hd = q.shape[0], w["hd"]
    p, kp = w["heads"] // 2, w["kv_heads"] // 2
    q = q.reshape(t, p, 2, hd)
    k = k.reshape(t, kp, 2, hd)
    v = v.reshape(t, kp, 2 * hd)
    pair_of = jnp.arange(p) // (p // kp)
    k_q, v_q = k[:, pair_of], v[:, pair_of]     # each query pair's key pair
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.dot(_f32(m["lq1"]), _f32(m["lk1"])))
           - jnp.exp(jnp.dot(_f32(m["lq2"]), _f32(m["lk2"]))) + lam_init)
    pos = jnp.arange(t)

    def rows(qb, pb):
        s = jnp.einsum("qphd,nphd->phqn", qb, k_q) / math.sqrt(hd)
        keep = pos[None, :] <= pb[:, None]
        if window is not None:
            keep &= pos[None, :] > pb[:, None] - window
        a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        o = jnp.einsum("phqn,npd->qphd", a, v_q)          # (q, p, 2, 2 hd)
        o = o[:, :, 0] - lam * o[:, :, 1]
        o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + w["eps"])
        return (o * _f32(m["subln"]) * (1.0 - lam_init)).reshape(qb.shape[0],
                                                                  -1)

    return _in_blocks(rows, QUERY_BLOCK, q, pos) @ _f32(m["Wo"])


def _in_blocks(fn, block, *rows):
    t = rows[0].shape[0]
    if t <= block or t % block:
        return fn(*rows)
    out = lax.map(lambda a: fn(*a), tuple(
        r.reshape((t // block, block) + r.shape[1:]) for r in rows))
    return out.reshape((t,) + out.shape[2:])


def round_fp8(a):
    """a rounded to float8 e4m3 (4 significand bits to bfloat16's 8),
    saturated at its largest finite value, and back to float32: the type
    below the configuration's."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    return jnp.clip(a, -top, top).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


def reference_hidden(cfg: dict, params, tokens, *, window=None,
                     rounding=None):
    """tokens (T,) int32 -> LN_f of the last layer's output (T, hidden),
    float32.  `window` replaces the configuration's sliding window, and
    `rounding`, a function of an array, is applied to the residual and to
    every normed activation — what a system that keeps them in a narrower
    type rounds (the controls of the comparison: the window layers over the
    whole context; the reference in the precision below the system's,
    `round_fp8`)."""
    w = widths(cfg)
    win = w["window"] if window is None else window
    r = rounding or (lambda a: a)
    dec = params[DECODER]
    x = r(_f32(params[EMBED]["W"])[tokens])
    kinds = layer_kinds(cfg)
    tap = max(i for i, k in enumerate(kinds[:kinds.index("full")])
              if k == "mamba")
    memory = full_kv = None
    for i, kind in enumerate(kinds):
        p = dec[f"layer{i:02d}"]
        m = p["mixer"]
        h = r(_layer_norm(x, p["ln1"], w["eps"]))
        if kind == "mamba":
            out, mem = _mamba(w, m, h)
            if i == tap:
                memory = mem
        elif kind == "gmu":
            out = (memory * jax.nn.silu(h @ _f32(m["W_1"]))) @ _f32(m["W_2"])
        else:
            q = h @ _f32(m["Wq"])
            if kind == "cross":
                k, v = full_kv
            else:
                k, v = h @ _f32(m["Wk"]), h @ _f32(m["Wv"])
            if kind == "full":
                full_kv = (k, v)
            out = _differential(w, m, i, q, k, v,
                                win if kind == "swa" else None)
        x = r(x + out)
        h = r(_layer_norm(x, p["ln2"], w["eps"]))
        gu = h @ _f32(p["ffn"]["W_in"])
        g, u = gu[:, :w["ff"]], gu[:, w["ff"]:]
        x = r(x + (jax.nn.silu(g) * u) @ _f32(p["ffn"]["W_out"]))
    return r(_layer_norm(x, dec["norm_f"], w["eps"]))


def _vocab_block(vocab: int) -> int:
    """The largest divisor of the vocabulary up to `VOCAB_BLOCK`."""
    return max(b for b in range(1, min(vocab, VOCAB_BLOCK) + 1)
               if vocab % b == 0)


def _head_gaps(emb, hidden, emitted):
    """Over blocks of the vocabulary: each row's largest logit, the logit
    of its emitted token, and the largest |logit| of all."""
    vocab, d = emb.shape
    blk = _vocab_block(vocab)

    def one(carry, at):
        best, chosen, top = carry
        z = hidden @ _f32(lax.dynamic_slice_in_dim(emb, at, blk)).T
        here = (emitted >= at) & (emitted < at + blk)
        picked = jnp.take_along_axis(
            z, jnp.clip(emitted - at, 0, blk - 1)[:, None], -1)[:, 0]
        return (jnp.maximum(best, z.max(-1)),
                jnp.where(here, picked, chosen),
                jnp.maximum(top, jnp.abs(z).max())), None

    n = hidden.shape[0]
    (best, chosen, top), _ = lax.scan(
        one, (jnp.full((n,), -jnp.inf), jnp.zeros((n,)), jnp.zeros(())),
        jnp.arange(0, vocab, blk))
    return best, chosen, top


def make_reference_gap(cfg: dict, *, window=None, rounding=None):
    """jitted (params, tokens (T,), rows (R,), emitted (R,)) -> (gap (R,),
    max|logit|): at each position `rows[i]`, how far the logit of the token
    the system emitted next sits below the reference's arg-max logit."""
    def gap(params, tokens, rows, emitted):
        with jax.default_matmul_precision("highest"):
            hidden = reference_hidden(cfg, params, tokens, window=window,
                                      rounding=rounding)[rows]
            best, chosen, top = _head_gaps(params[EMBED]["W"], hidden,
                                           emitted)
        return best - chosen, top

    return jax.jit(gap)


def make_reference_logits(cfg: dict, *, window=None):
    """jitted (params, tokens (T,)) -> logits (T, vocab) float32 (small
    vocabularies: the whole head at once)."""
    def logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            return reference_hidden(cfg, params, tokens,
                                    window=window) @ _f32(
                params[EMBED]["W"]).T

    return jax.jit(logits)


# -- analytic operation and byte counts ------------------------------------------
#
# REQUIRED floating-point operations (a multiply and an add each), from the
# shapes: what the mathematics above asks for, whatever the program does.

def _mixer_params(cfg: dict) -> dict:
    """Parameters of the matrices a token's row meets, by layer kind."""
    w = widths(cfg)
    d, e, hd = w["d"], w["inner"], w["hd"]
    q, kv = w["heads"] * hd, w["kv_heads"] * hd
    return {"mamba": d * 2 * e + e * (w["rank"] + 2 * w["state"])
                     + w["rank"] * e + e * d,
            "swa": d * q + 2 * d * kv + q * d,
            "full": d * q + 2 * d * kv + q * d,
            "gmu": 2 * d * e,
            "cross": d * q + q * d,
            "ffn": 3 * d * w["ff"]}


def rows_flops(cfg: dict, kinds, contexts) -> float:
    """FLOPs of rows at the contexts `contexts` (each row's own included)
    through layers of the kinds `kinds`: the matrices, the attention over
    the rows each attends (scores: heads x hd, values: heads x 2 hd per
    key; a window layer attends at most `sliding_window`), the recurrence
    (the state's update and read, 2 x d_state per channel) and the conv."""
    w = widths(cfg)
    m = _mixer_params(cfg)
    ctx = np.asarray(contexts, np.float64)
    per_key = 2.0 * w["heads"] * 3 * w["hd"]
    total = 0.0
    for kind in kinds:
        total += ctx.size * 2.0 * (m[kind] + m["ffn"])
        if kind == "swa":
            total += per_key * np.minimum(ctx, w["window"]).sum()
        elif kind in ("full", "cross"):
            total += per_key * ctx.sum()
        elif kind == "mamba":
            total += ctx.size * 2.0 * w["inner"] * (2 * w["state"]
                                                    + w["conv"])
    return float(total)


def _kv_projection_flops(cfg: dict) -> float:
    w = widths(cfg)
    return 2.0 * 2 * w["d"] * w["kv_heads"] * w["hd"]


def request_flops(cfg: dict, prompt_len: int, new_tokens: int,
                  held_share=None) -> float:
    """Required FLOPs of one completed request: the self-decoder and the
    full layer's keys and values over every prompt row; the full layer's
    attention onward over the prompt's LAST row only (nothing it computes
    for the others is used); every layer over the `new_tokens - 1`
    generated rows fed back (the last token never is); the head once per
    generated token.  `held_share`, the other families' share of expert
    assignments, has no use here (no experts)."""
    kinds = layer_kinds(cfg)
    at = kinds.index("full")
    w = widths(cfg)
    kv = _kv_projection_flops(cfg)
    return (rows_flops(cfg, kinds[:at], np.arange(1, prompt_len + 1))
            + (prompt_len - 1) * kv
            + rows_flops(cfg, kinds[at:], [prompt_len])
            + rows_flops(cfg, kinds, np.arange(prompt_len + 1,
                                               prompt_len + new_tokens))
            + new_tokens * 2.0 * w["d"] * w["vocab"])


def shared_kv_readers(cfg: dict) -> int:
    """Layers that read the shared K/V pool in a decode step: the full
    layer and every cross layer (the calls of ``shared_kv_attn`` a step)."""
    return sum(k in ("full", "cross") for k in layer_kinds(cfg))


def shared_kv_bytes(cfg: dict, rows: float, elem_bytes: int = 2) -> float:
    """Bytes the ``shared_kv_attn`` calls of one decode step must read for
    `rows` context rows summed over the live slots: each reader layer reads
    each row's keys and values once."""
    w = widths(cfg)
    return float(rows * shared_kv_readers(cfg) * 2 * w["kv_heads"] * w["hd"]
                 * elem_bytes)


def shared_kv_flops(cfg: dict, rows: float) -> float:
    """FLOPs of the same calls: per row and reader, every query half's
    score (hd) and its weighted value row (2 hd)."""
    w = widths(cfg)
    return float(2.0 * rows * shared_kv_readers(cfg) * w["heads"]
                 * (w["hd"] + 2 * w["hd"]))
