import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
# The dry-run (and ONLY the dry-run) builds the 512-placeholder-device
# production mesh; tests/benches see the real single CPU device.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract the roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both

Per cell this:
  1. builds ShapeDtypeStruct stand-ins (no allocation) for the train state
     / params+cache and the input batch,
  2. jits the step with explicit in/out shardings and ``.lower().compile()``
     against the (16,16) single-pod or (2,16,16) multi-pod mesh,
  3. records ``compiled.memory_analysis()`` (fits-per-chip evidence),
     ``compiled.cost_analysis()`` (FLOPs / bytes), and the collective
     traffic parsed from the post-SPMD HLO (every all-gather / all-reduce /
     reduce-scatter / all-to-all / collective-permute operand),
  4. writes a JSON artifact to ``dryrun_artifacts/<cell>.json``.

Sharding failures, non-divisible dims, or compile OOMs here are bugs in
the framework's distribution config — the cell list below must be green.
"""
import argparse
import json
import sys
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import SHAPES, TrainConfig
from repro.core.parallel import make_context
from repro.launch import hlo_cost
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import decode as dec
from repro.models import lm
from repro.train.step import make_train_step

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "dryrun_artifacts")


# ---------------------------------------------------------------------------
# Collective-traffic extraction from post-SPMD HLO
# ---------------------------------------------------------------------------
# The parser lives in repro.analysis.contracts now (stdlib-only import —
# safe before jax init), shared with the contract auditor's audit_hlo;
# re-exported here because the dry-run is its historical home.
from repro.analysis.contracts import parse_collectives  # noqa: E402,F401


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------

def build_cell(arch: str, shape_name: str, ctx, tc: TrainConfig):
    """Returns (jitted fn, arg ShapeDtypeStructs tuple)."""
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    batch_sds = S.batch_structs(cfg, shape)
    batch_spec = S.batch_specs(cfg, shape, ctx)
    sharding_of = lambda tree: jax.tree.map(ctx.sharding, tree)

    if shape.kind == "train":
        template, st_specs = S.state_spec_tree(cfg, tc, ctx)
        step = make_train_step(cfg, tc, ctx)
        fn = jax.jit(
            step,
            in_shardings=(sharding_of(st_specs), sharding_of(batch_spec)),
            out_shardings=(sharding_of(st_specs), None),
            donate_argnums=(0,),
        )
        return fn, (template, batch_sds)

    # inference cells share param structs/specs
    ptemplate = jax.eval_shape(
        lambda: lm.init_params(jax.random.key(0), cfg, tp_size=ctx.tp_size))
    pspecs = S.param_spec_tree(ptemplate, cfg, ctx)

    if shape.kind == "prefill":
        def prefill_fn(params, batch):
            cache, hidden = dec.prefill(
                params, batch["tokens"], cfg, ctx,
                frames=batch.get("frames"))
            logits = lm.lm_logits(params, hidden[:, -1:], cfg, ctx)
            return cache, jnp.argmax(logits[:, 0, : cfg.vocab_size],
                                     axis=-1).astype(jnp.int32)
        cache_t = jax.eval_shape(
            lambda: dec.init_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = S.cache_spec_tree(cache_t, cfg, ctx, shape.global_batch)
        fn = jax.jit(
            prefill_fn,
            in_shardings=(sharding_of(pspecs), sharding_of(batch_spec)),
            out_shardings=(sharding_of(cspecs),
                           ctx.sharding(S.P(ctx.dp_for(shape.global_batch)))),
        )
        return fn, (ptemplate, batch_sds)

    # decode: one token against a seq_len cache
    def serve_step(params, cache, tokens):
        cache, h = dec.decode_step(params, cache, tokens, cfg, ctx)
        logits = lm.lm_logits(params, h[:, None], cfg, ctx)[:, 0]
        nxt = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1)
        return cache, nxt.astype(jnp.int32)

    cache_t = jax.eval_shape(
        lambda: dec.init_cache(cfg, shape.global_batch, shape.seq_len))
    cspecs = S.cache_spec_tree(cache_t, cfg, ctx, shape.global_batch)
    fn = jax.jit(
        serve_step,
        in_shardings=(sharding_of(pspecs), sharding_of(cspecs),
                      ctx.sharding(S.P(ctx.dp_for(shape.global_batch)))),
        out_shardings=(sharding_of(cspecs),
                       ctx.sharding(S.P(ctx.dp_for(shape.global_batch)))),
        donate_argnums=(1,),
    )
    return fn, (ptemplate, cache_t, batch_sds["tokens"])


def cell_skip_reason(arch: str, shape_name: str):
    cfg = configs.get_config(arch)
    for s, skip in configs.shape_cells(arch):
        if s.name == shape_name:
            return skip
    return None


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = None, tc: TrainConfig = None) -> dict:
    out_dir = out_dir or ART_DIR
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
    skip = cell_skip_reason(arch, shape_name)
    record = {"arch": arch, "shape": shape_name,
              "multi_pod": multi_pod, "tag": tag}
    if skip:
        record["status"] = "skipped"
        record["skip_reason"] = skip
    else:
        tc = tc or TrainConfig(remat=True, optimizer_state_dtype="int8")
        mesh = make_production_mesh(multi_pod=multi_pod)
        ctx = make_context(mesh)
        with mesh:
            fn, args = build_cell(arch, shape_name, ctx, tc)
            compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo_text = compiled.as_text()
        corrected = hlo_cost.analyze(hlo_text)   # trip-count-weighted
        record.update({
            "status": "ok",
            "n_devices": mesh.devices.size,
            # raw XLA aggregates (scan bodies counted ONCE — see hlo_cost)
            "xla_flops_raw": float(cost.get("flops", -1)),
            "xla_bytes_raw": float(cost.get("bytes accessed", -1)),
            # trip-count-corrected per-device terms (roofline inputs)
            "flops_per_device": corrected["flops_per_device"],
            "hbm_bytes_per_device_approx":
                corrected["hbm_bytes_per_device_approx"],
            "collective_bytes_per_device":
                corrected["collective_bytes_per_device"],
            "collective_float_elems_per_device":
                corrected["collective_float_elems_per_device"],
            "hbm_float_elems_per_device":
                corrected["hbm_float_elems_per_device"],
            "hbm_other_bytes_per_device":
                corrected["hbm_other_bytes_per_device"],
            "collective_exec_counts": corrected["collective_exec_counts"],
            "has_unknown_trip_counts":
                corrected["has_unknown_trip_counts"],
            "memory_analysis": _mem_dict(mem),
        })
        coll_tot = sum(corrected["collective_bytes_per_device"].values())
        print(f"[{tag}] flops/dev={corrected['flops_per_device']:.3e}  "
              f"coll={coll_tot:.3e}B/dev")
        print(f"[{tag}] memory: {record['memory_analysis']}")
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(record, f, indent=1)
    return record


def _mem_dict(mem) -> dict:
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes", "host_generated_code_size_in_bytes",
              "host_argument_size_in_bytes", "host_output_size_in_bytes",
              "host_alias_size_in_bytes", "host_temp_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = str(mem)
    return out


def run_dlrm_cell(cache_rows: int = 0, cold_tier: str = "host",
                  out_dir: str = None, batch: int = 256) -> dict:
    """DLRM serving cell, routed ENTIRELY through ``DLRMConfig.cache``.

    ``cache_rows == 0``: lower + compile the distributed forward (the
    paper's RW pipeline) on the production mesh and record its collective
    traffic.  ``cache_rows > 0``: lower the TIERED serving program — the
    jitted forward over the flat (sum S_t, D) slot pool the engine
    scores with (cold tables off-HBM per ``cold_tier``) — and record
    that its HLO contains NO collectives and only pool-sized table
    memory: the whole trade the tiered store makes, as compile-time
    evidence.
    """
    import dataclasses as _dc

    from repro.cache import CacheConfig
    from repro.configs import dlrm as dlrm_cfg_mod
    from repro.core.jagged import JaggedBatch
    from repro.models import dlrm as dlrm_mod

    out_dir = out_dir or ART_DIR
    os.makedirs(out_dir, exist_ok=True)
    cfg = _dc.replace(dlrm_cfg_mod.smoke(),
                      cache=CacheConfig(rows=cache_rows,
                                        cold_tier=cold_tier))
    ecfg = cfg.embedding_config()
    tag = (f"dlrm__{'tiered' if cache_rows else 'rw'}"
           f"__{cold_tier if cache_rows else 'dist'}")
    T, R, D = ecfg.num_tables, ecfg.rows_per_table, ecfg.dim
    record = {"arch": "dlrm", "tag": tag, "cache_rows": cache_rows,
              "cold_tier": cold_tier if cache_rows else None}

    params_t = jax.eval_shape(
        lambda: dlrm_mod.init_params(jax.random.key(0), cfg))
    if cache_rows:
        # the engine's serving program: tables are the FLAT slot pool
        params_t = {**params_t,
                    "tables": jax.ShapeDtypeStruct((T * cache_rows, D),
                                                   jnp.float32)}
    dense_t = jax.ShapeDtypeStruct((batch, cfg.num_dense_features),
                                   jnp.float32)
    batch_t = JaggedBatch(
        jax.ShapeDtypeStruct((T, batch, cfg.pooling), jnp.int32),
        jax.ShapeDtypeStruct((T, batch), jnp.int32))

    if cache_rows:
        fn = jax.jit(lambda p, d, b: dlrm_mod.forward(p, d, b, cfg, None))
        compiled = fn.lower(params_t, dense_t, batch_t).compile()
    else:
        mesh = make_production_mesh(multi_pod=False)
        ctx = make_context(mesh)
        with mesh:
            fn = jax.jit(
                lambda p, d, b: dlrm_mod.forward(p, d, b, cfg, ctx))
            compiled = fn.lower(params_t, dense_t, batch_t).compile()
    coll, counts = parse_collectives(compiled.as_text())
    record.update({
        "status": "ok",
        "table_bytes": T * (cache_rows or R) * D * 4,
        "collective_bytes": coll,
        "collective_counts": counts,
        "memory_analysis": _mem_dict(compiled.memory_analysis()),
    })
    if cache_rows:
        # collective-free serving contract, audited over the compiled HLO
        from repro.analysis.contracts import audit_hlo
        from repro.serving.engine import KERNEL_CONTRACTS
        audit_hlo(compiled.as_text(),
                  KERNEL_CONTRACTS["tiered_forward"]).raise_if_failed()
    print(f"[{tag}] table/pool bytes {record['table_bytes']:.3e}  "
          f"collectives {counts}")
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(record, f, indent=1)
    return record


def all_cells():
    for arch in configs.ARCH_IDS:
        for shape_name in SHAPES:
            yield arch, shape_name


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["no", "yes", "both"],
                    default="no")
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--opt-state-dtype", default="int8")
    ap.add_argument("--dlrm", action="store_true",
                    help="run the DLRM serving cells (distributed RW "
                         "vs tiered slot-pool program) instead of the "
                         "LM arch grid")
    ap.add_argument("--dlrm-cache-rows", type=int, default=64)
    ap.add_argument("--dlrm-cold-tier", default="host",
                    choices=["host", "remote"])
    args = ap.parse_args(argv)

    if args.dlrm:
        run_dlrm_cell(0, out_dir=args.out_dir)
        run_dlrm_cell(args.dlrm_cache_rows, args.dlrm_cold_tier,
                      out_dir=args.out_dir)
        print("dlrm dry-run complete")
        return

    pods = {"no": [False], "yes": [True], "both": [False, True]}[args.multi_pod]
    tc = TrainConfig(remat=True, optimizer_state_dtype=args.opt_state_dtype)
    cells = (list(all_cells()) if args.all
             else [(args.arch, args.shape)])
    failures = []
    for arch, shape_name in cells:
        for mp in pods:
            try:
                run_cell(arch, shape_name, mp, args.out_dir, tc)
            except Exception:
                failures.append((arch, shape_name, mp))
                traceback.print_exc()
    if failures:
        print("FAILED CELLS:", failures)
        sys.exit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
