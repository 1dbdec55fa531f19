#!/usr/bin/env python3
"""Compile the cells' programs for a TPU v5e that is described, not attached.

    JAX_PLATFORMS=cpu python benchmarks/tools/offline_compile.py \
        [--cells NAME ...] [--only train|decode|prefill] [--out FILE]

libtpu compiles for a topology without a chip
(`jax.experimental.topologies`), so what the chip's compiler would refuse —
a kernel that does not lower, a program that does not fit 16 GB — costs no
chip time.  For each program this prints `memory_analysis()` and whether
the Pallas kernels are in it (`tpu_custom_call`).  The numbers fix the
training batch, `slots` and `num_pages` in the traffic files before any
chip run.  Nothing runs, so nothing here is a time or a rate.

The package decides bf16 / flash / the paged kernel from
`backend().is_tpu`, which sees the CPU here; this script (and only this
script) replaces `backend()` with a TPU answer before the package's
modules import it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import (  # noqa: E402
    NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

jax.config.update("jax_enable_compilation_cache", False)


def pretend_tpu() -> None:
    """Must run before `deeplearning4j_tpu.models` is imported: those
    modules bind `backend` by name."""
    import importlib

    bk = importlib.import_module("deeplearning4j_tpu.runtime.backend")
    fake = bk.Backend(platform="tpu", device_kind="TPU v5 lite",
                      num_devices=1, supports_bfloat16_matmul=True)
    bk.backend = lambda: fake


def with_sharding(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def report(name: str, compiled, t0: float) -> dict:
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    row = {
        "program": name,
        "compile_s": round(time.perf_counter() - t0, 1),
        "argument_gb": round(ma.argument_size_in_bytes / 1e9, 3),
        "output_gb": round(ma.output_size_in_bytes / 1e9, 3),
        "alias_gb": round(ma.alias_size_in_bytes / 1e9, 3),
        "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
        "code_gb": round(ma.generated_code_size_in_bytes / 1e9, 3),
        "pallas_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count(" all-reduce("),
    }
    # live at once: arguments + outputs that do not alias them + temps
    row["peak_gb"] = round(row["argument_gb"] + row["output_gb"]
                           - row["alias_gb"] + row["temp_gb"]
                           + row["code_gb"], 3)
    print(json.dumps(row), flush=True)
    return row


def load_cell(name: str):
    from benchmarks.harness import spec

    cell = spec.load(ROOT).cell(name)
    return cell, spec.family(cell.config)


def train_step(cell_name: str, topo, batch_per_chip: int | None):
    """The step program `fit()` dispatches, at the cell's shapes."""
    from deeplearning4j_tpu.runtime.mesh import active_mesh_scope

    cell, fam = load_cell(cell_name)
    cfg, tr = cell.config, cell.traffic
    n = int(tr["parallel"]["data"])
    b = (batch_per_chip or int(tr["batch_per_chip"])) * n
    t = int(tr["seq_len"])
    model = fam.build_model(cfg)

    def traced():
        model.init()
        return model.params, model.net_state, model.opt_state

    params, net_state, opt_state = jax.eval_shape(traced)
    model.params = model.net_state = model.opt_state = None
    mesh = None
    if n == 1:
        rep = batch = SingleDeviceSharding(topo.devices[0])
    else:
        from deeplearning4j_tpu.parallel import ParallelConfig

        mesh = ParallelConfig(data=n).build_mesh(list(topo.devices)[:n])
        rep, batch = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        model._mesh = mesh
    ids = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=batch)
    empty = jax.ShapeDtypeStruct((0,), jnp.float32, sharding=rep)
    step = model._get_step_fn(False, False, False).__wrapped__
    t0 = time.perf_counter()
    with active_mesh_scope(mesh):
        compiled = step.lower(
            with_sharding(params, rep), with_sharding(opt_state, rep),
            with_sharding(net_state, rep),
            jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep),
            ids, ids, empty, empty, {}).compile()
    return report(f"{cell_name}: train step batch {b} x {t} on {n} chip(s)",
                  compiled, t0)


def serve_engine(cell_name: str):
    from deeplearning4j_tpu.serving.generation import (
        GenerationConfig, GenerationEngine,
    )

    cell, fam = load_cell(cell_name)
    model = fam.build_model(cell.config)
    model.params = fam.abstract_params(cell.config)
    model.net_state = {}
    eng_cfg = dict(cell.traffic["engine"])
    num_pages = int(eng_cfg["num_pages"])
    eng = GenerationEngine(model=model, config=GenerationConfig(
        **{**eng_cfg, "num_pages": 2}))
    return cell, model, eng, num_pages


def decode_step(cell_name: str, topo, num_pages: int | None,
                slots: int | None):
    cell, model, eng, pages = serve_engine(cell_name)
    one = SingleDeviceSharding(topo.devices[0])
    num_pages = num_pages or pages
    if slots:
        eng.config.slots = slots
    s, mp = eng.config.slots, eng.config.max_pages_per_seq
    kv = eng.kv
    pool = jax.ShapeDtypeStruct(
        (kv.n_layers, num_pages, kv.page_size, kv.n_heads, kv.head_dim),
        jnp.float32, sharding=one)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    t0 = time.perf_counter()
    compiled = eng._make_step().lower(
        with_sharding(model.params, one), pool, pool, None, None,
        i32(s, mp), i32(s), i32(s),
        jax.ShapeDtypeStruct((s,), jnp.uint32, sharding=one), i32(s),
        jax.ShapeDtypeStruct((s,), jnp.float32, sharding=one), i32(s),
    ).compile()
    row = report(f"{cell_name}: decode step slots {s} x {mp} pages, pool "
                 f"{num_pages} pages", compiled, t0)
    row["pool_gb"] = round(2 * np.prod(pool.shape) * 4 / 1e9, 3)
    print(json.dumps({"pool_k_plus_v_gb": row["pool_gb"]}), flush=True)
    return row


def prefill(cell_name: str, topo, buckets):
    from benchmarks.harness import traffic as tg

    cell, model, eng, _ = serve_engine(cell_name)
    one = SingleDeviceSharding(topo.devices[0])
    rows = []
    for t_b in buckets or tg.prefill_buckets(cell.traffic):
        sc = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one)
        t0 = time.perf_counter()
        compiled = eng._make_prefill(t_b).lower(
            with_sharding(model.params, one),
            jax.ShapeDtypeStruct((1, t_b), jnp.int32, sharding=one),
            sc(jnp.int32), sc(jnp.uint32), sc(jnp.float32), sc(jnp.int32),
        ).compile()
        rows.append(report(f"{cell_name}: prefill bucket {t_b}", compiled, t0))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--batch-per-chip", type=int, default=None)
    ap.add_argument("--num-pages", type=int, default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--buckets", type=int, nargs="*", default=None)
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    pretend_tpu()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    from benchmarks.harness import spec

    rows = []
    for cell in spec.load(ROOT).cells:
        if args.cells and cell.name not in args.cells:
            continue
        kind = cell.traffic["kind"]
        if kind == "train" and args.only in ("", "train"):
            rows.append(train_step(cell.name, topo, args.batch_per_chip))
        if kind == "serve" and args.only in ("", "decode"):
            rows.append(decode_step(cell.name, topo, args.num_pages,
                                    args.slots))
        if kind == "serve" and args.only in ("", "prefill"):
            rows.extend(prefill(cell.name, topo, args.buckets))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"topology": "v5e:2x2", "jax": jax.__version__,
                       "note": "offline compile; nothing ran on a chip",
                       "programs": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
