import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers + compiles the real step function (train_step / prefill /
     decode_step) with NamedSharding-annotated inputs (ShapeDtypeStruct
     stand-ins — no allocation),
  3. prints ``memory_analysis()`` (fits-per-device proof) and
     ``cost_analysis()``, and takes a census of the collective schedule,
  4. (single-pod) compiles the roofline *cost components* — per-kind layer
     step, embed/loss ends, optimizer — and combines them into the three
     roofline terms (launch/roofline.py explains why components are needed:
     XLA counts scan bodies once).

Results stream into a JSON report consumed by EXPERIMENTS.md and by
``repro.serving.profiles`` (the MDInference zoo's latency priors).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out roofline.json
"""
import argparse
import dataclasses
import json
import time
import traceback
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.archs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, input_specs, skip_reason
from repro.distributed.api import axis_rules, named_sharding
from repro.launch.mesh import make_custom_mesh, make_production_mesh, make_rules
from repro.launch import roofline as rf
from repro.models import transformer as T
from repro.training.optimizer import OptimizerConfig, adamw_update, init_opt_state
from repro.training.train_loop import (
    TrainConfig,
    init_train_state,
    make_train_step,
    state_shardings,
)

OPT_CFG = OptimizerConfig()


# ---------------------------------------------------------------------------
# Step builders (full scanned step — the compile artifact).
# ---------------------------------------------------------------------------
def _tune_cfg(cfg, shape):
    """Execution knobs for production shapes (architecture unchanged)."""
    over = {"remat": True}
    if "moe" in cfg.pattern:
        # One group per batch row: groups stay sharded exactly like the batch
        # (a group layout that crosses the batch sharding makes GSPMD fall
        # back to full replication of the token array — measured +4 GiB/dev
        # per MoE layer).  The tensor axis parallelizes inside the experts.
        over["moe_groups"] = SHAPES[shape].global_batch
    return dataclasses.replace(cfg, **over)


def build_cell(cfg, shape, mesh, rules, microbatches=1):
    """Returns (jitted_fn, example_args) for the cell's step function."""
    cell = SHAPES[shape]
    specs = input_specs(cfg, shape)
    p_sh = jax.tree.map(
        lambda ax: named_sharding(mesh, rules, ax),
        T.param_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )
    params_sds = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    batch_sh = named_sharding(mesh, rules, ("batch",))

    if cell.kind == "train":
        step = make_train_step(
            cfg, OPT_CFG, TrainConfig(microbatches=microbatches),
            mesh=mesh, rules=rules,
        )
        state_sds = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.key(0))
        )
        return step, (state_sds, specs["inputs"])

    if cell.kind == "prefill":
        def prefill_fn(params, inputs):
            with axis_rules(rules):
                return T.prefill(cfg, params, inputs, max_len=cell.seq_len)

        fn = jax.jit(prefill_fn, in_shardings=(p_sh, batch_sh))
        return fn, (params_sds, specs["inputs"])

    # decode
    c_sh = jax.tree.map(
        lambda ax: named_sharding(mesh, rules, ax),
        T.cache_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )

    def decode_fn(params, cache, token, pos):
        with axis_rules(rules):
            return T.decode_step(cfg, params, cache, token, pos)

    fn = jax.jit(
        decode_fn,
        in_shardings=(p_sh, c_sh, batch_sh, batch_sh),
        donate_argnums=(1,),
    )
    return fn, (params_sds, specs["cache"], specs["token"], specs["pos"])


# ---------------------------------------------------------------------------
# Cost components (single-pod roofline).
# ---------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _block_params_sds(cfg, kind):
    def leaf(path, spec):
        shape, _ = spec
        name = path[-1]
        dt = jnp.float32 if T._fp32_leaf(name) else jnp.dtype(cfg.dtype)
        return _sds(shape, dt)

    return T._walk_spec(T.block_spec(cfg, kind), leaf)


def _block_shardings(cfg, kind, mesh, rules):
    def leaf(path, spec):
        _, ax = spec
        return named_sharding(mesh, rules, ax)

    return T._walk_spec(T.block_spec(cfg, kind), leaf)


def cost_components(cfg, shape, mesh, rules):
    """[(name, compiled, multiplier)] for the roofline combination."""
    cell = SHAPES[shape]
    cfgu = dataclasses.replace(cfg, unroll_scans=True, remat=False)
    B = cell.global_batch
    S = cell.seq_len
    dtype = jnp.dtype(cfg.dtype)
    counts = Counter(cfg.layer_kinds())
    batch_sh = named_sharding(mesh, rules, ("batch",))
    x_sh = named_sharding(mesh, rules, ("batch", "seq_act", None))
    comps = []

    kind_mode = "train" if cell.kind == "train" else cell.kind
    pos_sds = _sds((B, 1 if cell.kind == "decode" else S), jnp.int32)

    for kind, count in counts.items():
        bp_sds = _block_params_sds(cfgu, kind)
        bp_sh = _block_shardings(cfgu, kind, mesh, rules)
        if cell.kind == "decode" and kind == "slstm":
            continue  # decode slstm cost covered by the generic path below
        if kind == "slstm" and cell.kind != "decode":
            # Sequential cell: compile ONE timestep, scale by S * count.
            from repro.models import xlstm

            def slstm_one(bp, xt, st):
                with axis_rules(rules):
                    st2 = xlstm._slstm_step(bp["cell"], cfgu.xlstm_heads, xt, st)
                    return sum(jnp.sum(v * v) for v in st2.values())

            xt_sds = _sds((B, cfgu.d_model), jnp.float32)
            st_sds = {k: _sds((B, cfgu.d_model), jnp.float32) for k in "cnhm"}
            fn = (
                jax.value_and_grad(slstm_one)
                if cell.kind == "train"
                else slstm_one
            )
            compiled = (
                jax.jit(fn, in_shardings=(bp_sh, x_sh if False else batch_sh, None))
                .lower(bp_sds, xt_sds, st_sds)
                .compile()
            )
            comps.append((f"slstm_step", compiled, float(S * count)))
            continue

        ctx_decode = cell.kind == "decode"
        s_len = 1 if ctx_decode else S
        mult = float(count)
        if kind == "mlstm" and not ctx_decode:
            # mLSTM cost is linear in chunk count (projections + fixed-size
            # quadratic chunks); compile a short sequence and scale, instead
            # of unrolling S/chunk (512 at 32k) chunk bodies.
            s_len = min(S, cfgu.xlstm_chunk * 8)
            mult = float(count) * (S / s_len)
        x_sds = _sds((B, s_len, cfgu.d_model), dtype)
        kpos_sds = _sds((B, s_len), jnp.int32)
        cache_sds = (
            jax.eval_shape(lambda: T._block_cache(cfgu, kind, B, S, dtype))
            if ctx_decode
            else None
        )

        def block_fn(bp, x, pos, cache=None, kind=kind):
            with axis_rules(rules):
                from repro.distributed.api import constrain

                ctx = T.SeqContext(positions=pos, decode=ctx_decode)
                out, _, aux = T.apply_block(cfgu, kind, bp, x, ctx, cache)
                if not ctx_decode:  # period-boundary layout (SP variants)
                    out = constrain(out, "batch", "seq_act", None)
                return jnp.sum(out.astype(jnp.float32) ** 2) + aux

        if cell.kind == "train":
            fn = jax.value_and_grad(block_fn)
        else:
            fn = block_fn
        in_sh = (bp_sh, x_sh, batch_sh) + ((None,) if ctx_decode else ())
        args = (bp_sds, x_sds, kpos_sds) + ((cache_sds,) if ctx_decode else ())
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        comps.append((f"block_{kind}", compiled, mult))

    # Ends: embedding + final norm + loss/logits with a 0-layer config.
    cfg0 = dataclasses.replace(cfgu, n_layers=0)
    p0_sds = jax.eval_shape(lambda: T.init_params(cfg0, jax.random.key(0)))
    p0_sh = jax.tree.map(
        lambda ax: named_sharding(mesh, rules, ax),
        T.param_axes(cfg0),
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )
    specs = input_specs(cfg0, shape)
    if cell.kind == "train":
        def ends_fn(p, b):
            with axis_rules(rules):
                return T.loss_fn(cfg0, p, b)[0]

        compiled = (
            jax.jit(jax.value_and_grad(ends_fn), in_shardings=(p0_sh, batch_sh))
            .lower(p0_sds, specs["inputs"])
            .compile()
        )
    elif cell.kind == "prefill":
        def ends_fn(p, b):
            with axis_rules(rules):
                return T.prefill(cfg0, p, b, max_len=cell.seq_len)

        compiled = (
            jax.jit(ends_fn, in_shardings=(p0_sh, batch_sh))
            .lower(p0_sds, specs["inputs"])
            .compile()
        )
    else:
        cache0 = jax.eval_shape(lambda: T.init_cache(cfg0, B, S))

        def ends_fn(p, c, tok, pos):
            with axis_rules(rules):
                return T.decode_step(cfg0, p, c, tok, pos)

        compiled = (
            jax.jit(ends_fn, in_shardings=(p0_sh, None, batch_sh, batch_sh))
            .lower(p0_sds, cache0, specs["token"], specs["pos"])
            .compile()
        )
    comps.append(("ends", compiled, 1.0))

    # Optimizer update (train only).
    if cell.kind == "train":
        params_sds = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
        opt_sds = jax.eval_shape(lambda: init_opt_state(params_sds))
        grads_sds = jax.tree.map(lambda p: _sds(p.shape, jnp.float32), params_sds)

        def opt_fn(p, g, o):
            with axis_rules(rules):
                return adamw_update(OPT_CFG, p, g, o)[:2]

        p_sh = jax.tree.map(
            lambda ax: named_sharding(mesh, rules, ax),
            T.param_axes(cfg),
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x),
        )
        compiled = (
            jax.jit(opt_fn, in_shardings=(p_sh, p_sh, {"mu": p_sh, "nu": p_sh, "step": None}))
            .lower(params_sds, grads_sds, opt_sds)
            .compile()
        )
        comps.append(("optimizer", compiled, 1.0))

    return comps


def model_flops(cfg, shape) -> float:
    cell = SHAPES[shape]
    n_active = cfg.param_count(active_only=True)
    tokens = cell.global_batch * (1 if cell.kind == "decode" else cell.seq_len)
    mult = 6 if cell.kind == "train" else 2
    return float(mult * n_active * tokens)


# ---------------------------------------------------------------------------
# Driver.
# ---------------------------------------------------------------------------
def run_cell(arch, shape, mesh_kind, *, with_components=True, verbose=True,
             seq_parallel=False, decode_opt=False, mesh_shape=None, variant="",
             microbatches=1, kv_quant=False):
    cfg = _tune_cfg(get_config(arch), shape)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_cache_quant=True)
    reason = skip_reason(cfg, shape)
    if reason:
        return {
            "arch": arch, "shape": shape, "mesh": mesh_kind,
            "status": "skipped", "note": reason, "variant": variant,
        }
    multi = mesh_kind == "multi_pod"
    if mesh_shape:
        mesh = make_custom_mesh(*mesh_shape)
    else:
        mesh = make_production_mesh(multi_pod=multi)
    rules = make_rules(mesh, seq_parallel=seq_parallel, decode_opt=decode_opt)
    # Small-batch decode (long_500k: global_batch=1): the batch dim cannot
    # cover the data axes; replicate it — seq_kv/TP carry the parallelism.
    cell = SHAPES[shape]
    data_size = int(
        np.prod([mesh.shape[a] for a in mesh.axis_names if a != "model"])
    )
    if cell.kind == "decode" and cell.global_batch < data_size:
        from repro.distributed.api import AxisRules

        table = dict(rules.table)
        table["batch"] = None
        table["moe_groups"] = None
        rules = AxisRules(mesh, table)
    chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    fn, args = build_cell(cfg, shape, mesh, rules, microbatches=microbatches)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
    }
    mem["per_device_total"] = (
        mem["argument_bytes"] + mem["temp_bytes"] + mem["output_bytes"]
        - mem["alias_bytes"]
    )
    hlo = compiled.as_text()
    census = dict(
        Counter(
            m.group(0)
            for m in __import__("re").finditer(
                r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\b",
                hlo,
            )
        )
    )
    full_ca = compiled.cost_analysis()

    row = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "variant": variant,
        "chips": chips,
        "global_batch": SHAPES[shape].global_batch,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem,
        "collective_census": census,
        "full_step_cost_analysis": {
            "flops": float(full_ca.get("flops", 0)),
            "bytes": float(full_ca.get("bytes accessed", 0)),
        },
    }

    if with_components and mesh_kind == "single_pod":
        comps = []
        for name, compiled_c, mult in cost_components(cfg, shape, mesh, rules):
            comps.append(
                rf.component_from_compiled(name, compiled_c, multiplier=mult)
            )
        totals = rf.combine_components(comps)
        terms = rf.cost_terms(totals, chips)
        mf = model_flops(cfg, shape)
        row.update(
            {
                "terms_s": terms,
                "totals": {k: v for k, v in totals.items() if k != "coll_by_kind"},
                "coll_by_kind": totals["coll_by_kind"],
                "model_flops": mf,
                # cost_analysis is per-device (post-SPMD module)
                "model_flops_over_hlo": mf / max(totals["flops"] * chips, 1.0),
                "dominant": max(terms, key=lambda k: terms[k]),
                "components": [
                    {"name": c.name, "flops": c.flops, "mult": c.multiplier}
                    for c in comps
                ],
            }
        )
    if verbose:
        dom = row.get("dominant", "-")
        print(
            f"[{mesh_kind}] {arch:24s} {shape:12s} compile={t_compile:6.1f}s "
            f"mem/dev={mem['per_device_total']/2**30:6.2f}GiB "
            f"census={census} dom={dom}",
            flush=True,
        )
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/roofline.json")
    ap.add_argument("--no-components", action="store_true")
    ap.add_argument("--sp", action="store_true", help="sequence-parallel rules")
    ap.add_argument("--decode-opt", action="store_true",
                    help="weight-stationary decode rules")
    ap.add_argument("--mesh-shape", default="", help="e.g. 64x4 (single pod)")
    ap.add_argument("--variant", default="", help="label recorded per row")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {
        "single": ["single_pod"],
        "multi": ["multi_pod"],
        "both": ["single_pod", "multi_pod"],
    }[args.mesh]

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    cells = []
    if out_path.exists():
        cells = json.loads(out_path.read_text()).get("cells", [])
    done = {(c["arch"], c["shape"], c["mesh"], c.get("variant", "")) for c in cells}

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                if (arch, shape, mesh_kind, args.variant) in done:
                    continue
                try:
                    mesh_shape = None
                    if args.mesh_shape:
                        d, m = args.mesh_shape.split("x")
                        mesh_shape = (int(d), int(m))
                    row = run_cell(
                        arch, shape, mesh_kind,
                        with_components=not args.no_components,
                        seq_parallel=args.sp,
                        decode_opt=args.decode_opt,
                        mesh_shape=mesh_shape,
                        variant=args.variant,
                    )
                except Exception as e:  # record failures: they are bugs
                    traceback.print_exc()
                    row = {
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "variant": args.variant,
                        "status": "error", "note": f"{type(e).__name__}: {e}",
                    }
                cells.append(row)
                out_path.write_text(json.dumps({"cells": cells}, indent=1))

    ok = sum(1 for c in cells if c["status"] == "ok")
    skip = sum(1 for c in cells if c["status"] == "skipped")
    err = sum(1 for c in cells if c["status"] == "error")
    print(f"\n=== dry-run: {ok} ok / {skip} skipped / {err} errors -> {out_path}")
    return 1 if err else 0


if __name__ == "__main__":
    raise SystemExit(main())
