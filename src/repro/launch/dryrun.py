import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# ^ MUST run before any jax import: jax locks the device count on first init.

"""Multi-pod dry-run (deliverable e): for every (architecture x input shape x
mesh), lower + compile the real step function -- train_step for train cells,
prefill for prefill cells, serve_step (one token against a seq_len KV cache)
for decode cells -- on the 16x16 single-pod and 2x16x16 multi-pod meshes,
then record memory_analysis / cost_analysis / per-collective bytes for the
roofline (EXPERIMENTS.md §Dry-run, §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
      --shape train_4k --mesh single --out experiments/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all  (full sweep, serial)
"""
import argparse
import json
import re
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import all_archs, get_config, get_shapes
from repro.distributed.sharding import logical_to_spec, rules_for, spec_tree
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh, num_chips)
from repro.models import build_model
from repro.models.api import abstract_cache, abstract_init, input_specs
from repro.training.optimizer import AdamW
from repro.training.train_loop import make_train_step

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_SHAPE_RE = re.compile(r"(bf16|f32|f16|f8\w*|s32|s8|u32|u8|s64|u64|pred|s16|u16)"
                       r"\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
          "s64": 8, "u64": 8, "pred": 1, "s16": 2, "u16": 2}


_COLL_RE = re.compile(
    r"=\s*([^=]*?)\s(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")


def _shape_bytes(text):
    nbytes = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        nbytes += n * _BYTES.get(dt, 4)
    return nbytes


def collective_bytes(hlo_text):
    """Per-collective result-shape bytes from the (post-SPMD, per-device)
    HLO text. HLO line format: `%name = <result shape> <opcode>(operands)`.
    The `-done` halves of async pairs are skipped so pairs count once."""
    out = {c: 0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or m.group(3) == "-done":
            continue
        op = m.group(2)
        out[op] += _shape_bytes(m.group(1))
        counts[op] += 1
    out["counts"] = counts
    return out


def _mem_dict(mem) -> Dict[str, int]:
    return {k: getattr(mem, k) for k in
            ("generated_code_size_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "temp_size_in_bytes",
             "alias_size_in_bytes")}


def build_cell(arch: str, shape_name: str, *, multi_pod: bool,
               overrides: Optional[Dict[str, Any]] = None):
    """Returns (jitted_fn, example_args, meta) for one dry-run cell."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**{k: v for k, v in overrides.items()
                             if hasattr(cfg, k)})
    cell = next(s for s in get_shapes(arch) if s.name == shape_name)
    if cell.skip:
        return None, None, {"skip": cell.skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    rules = rules_for(cfg, mesh)
    # batch too small to split over (pod x data) (e.g. long_500k B=1):
    # serve it batch-replicated, TP still applies
    deg = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            deg *= mesh.shape[ax]
    if cell.global_batch % deg:
        rules = dict(rules, batch=None)
    pshapes, plogical = abstract_init(model)
    pspecs = spec_tree(plogical, rules)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    batch_sh = NamedSharding(mesh, logical_to_spec(("batch", "seq"), rules))
    tok1_sh = NamedSharding(mesh, logical_to_spec(("batch",), rules))
    specs = input_specs(cfg, cell)
    meta = {"arch": arch, "shape": shape_name, "kind": cell.kind,
            "family": cfg.family,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "seq_len": cell.seq_len, "global_batch": cell.global_batch,
            "params": cfg.param_count(),
            "active_params": cfg.param_count(active_only=True)}

    if cell.kind == "train":
        opt = AdamW(moment_dtype=jnp.bfloat16 if cfg.fsdp else jnp.float32)
        # microbatch count: per-arch default, capped so every microbatch still
        # spans the full batch-sharding degree (pod x data)
        shard_deg = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                shard_deg *= mesh.shape[ax]
        accum = (overrides or {}).get("accum", cfg.train_accum)
        accum = max(1, min(accum, cell.global_batch // shard_deg))
        while cell.global_batch % (accum * shard_deg):
            accum -= 1
        meta["accum"] = accum
        bps = {k: logical_to_spec(("batch", "seq"), rules) for k in specs}
        if cfg.family == "vlm":
            bps["image_embeds"] = logical_to_spec(("batch", None, None), rules)
        step = make_train_step(model, opt, accum=accum, batch_pspecs=bps)
        oshapes = jax.eval_shape(opt.init, pshapes)
        oshard = {"mu": pshard, "nu": pshard,
                  "step": NamedSharding(mesh, P())}
        in_sh = (pshard, oshard, {k: batch_sh for k in specs})
        if cfg.family == "vlm":
            in_sh[2]["image_embeds"] = NamedSharding(
                mesh, logical_to_spec(("batch", None, None), rules))
        fn = jax.jit(step, in_shardings=in_sh,
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))
        args = (pshapes, oshapes, specs)
    elif cell.kind == "prefill":
        cshapes, clogical = abstract_cache(model, cell.global_batch, cell.seq_len)
        cspecs = spec_tree(clogical, rules)
        cshard = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                              is_leaf=lambda x: isinstance(x, P))

        if cfg.family == "vlm":
            def prefill_fn(params, tokens, cache, image_embeds):
                return model.prefill(params, tokens, cache,
                                     image_embeds=image_embeds)
            in_sh = (pshard, batch_sh, cshard, NamedSharding(
                mesh, logical_to_spec(("batch", None, None), rules)))
            args = (pshapes, specs["tokens"], cshapes, specs["image_embeds"])
        else:
            def prefill_fn(params, tokens, cache):
                return model.prefill(params, tokens, cache)
            in_sh = (pshard, batch_sh, cshard)
            args = (pshapes, specs["tokens"], cshapes)
        fn = jax.jit(prefill_fn, in_shardings=in_sh,
                     out_shardings=(cshard, None), donate_argnums=(2,))
    else:  # decode
        cshapes, clogical = abstract_cache(model, cell.global_batch, cell.seq_len)
        cspecs = spec_tree(clogical, rules)
        cshard = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                              is_leaf=lambda x: isinstance(x, P))

        def serve_step(params, tokens, cache):
            from repro.serving.sampler import mask_padded_vocab
            cache, logits = model.decode_step(params, tokens, cache)
            logits = mask_padded_vocab(logits, cfg.vocab)
            return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        fn = jax.jit(serve_step, in_shardings=(pshard, tok1_sh, cshard),
                     out_shardings=(cshard, tok1_sh), donate_argnums=(2,))
        args = (pshapes, specs["tokens"], cshapes)
    return (fn, args, meta), mesh, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             overrides: Optional[Dict[str, Any]] = None,
             print_analysis: bool = True,
             probes: bool = True) -> Dict[str, Any]:
    built, mesh, meta = build_cell(arch, shape_name, multi_pod=multi_pod,
                                   overrides=overrides)
    if built is None:
        return meta
    fn, args, meta = built
    chips = num_chips(mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()

    # XLA cost_analysis counts while (scan) bodies ONCE, not x trip count, so
    # per-device costs are recovered by exploiting that cost is affine in the
    # layer count: two probe compiles at reduced depth give the exact slope.
    cfg_full = get_config(arch)
    if overrides:
        cfg_full = cfg_full.replace(**{k: v for k, v in overrides.items()
                                       if hasattr(cfg_full, k)})
    L_full = cfg_full.num_layers
    if cfg_full.family == "vlm":
        L1, L2 = cfg_full.cross_attn_every, 2 * cfg_full.cross_attn_every
    elif cfg_full.family == "hybrid":
        tail = cfg_full.num_layers - (cfg_full.num_layers // 3) * 3
        L1, L2 = 3 + tail, 6 + tail
    else:
        L1, L2 = 2, 4

    def probe(L):
        from repro.models import layers as _layers
        ovr = dict(overrides or {})
        ovr["num_layers"] = L
        # accum=1 is cost-equivalent (same tokens, same single grad-reduce)
        # and avoids unrolling the accumulation scan in the probe HLO
        ovr["accum"] = 1
        b, m2, _ = build_cell(arch, shape_name, multi_pod=multi_pod,
                              overrides=ovr)
        pfn, pargs, _ = b
        _layers.SCAN_UNROLL = True   # trip-count-correct cost_analysis
        try:
            with jax.set_mesh(m2):
                pl = pfn.lower(*pargs)
        finally:
            _layers.SCAN_UNROLL = False
        with jax.set_mesh(m2):
            pc = pl.compile()
        cost = pc.cost_analysis()
        coll = collective_bytes(pc.as_text())
        return {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": {k: float(v) for k, v in coll.items() if k != "counts"},
            "coll_counts": coll["counts"],
        }

    if not probes:
        # gate-only mode (multi-pod pass): prove lower+compile succeeds and
        # record memory; roofline terms come from the single-pod table.
        result = dict(meta)
        result.update({
            "chips": chips,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": _mem_dict(mem),
            "gate_only": True,
        })
        if print_analysis:
            print(f"== {arch} / {shape_name} / {result['mesh']} COMPILED "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
            print(f"   memory_analysis: {result['memory']}")
        return result

    if L_full == L1:
        p1 = p2 = probe(L1)
        L2 = L1 + 1  # degenerate; slope 0
    else:
        p1, p2 = probe(L1), probe(L2)

    def affine(c1, c2):
        slope = (c2 - c1) / (L2 - L1)
        # clamp: XLA occasionally switches SPMD strategy between probe depths
        # (non-affine); a negative extrapolation is reported as 0.
        return max(c1 + slope * (L_full - L1), 0.0)

    flops_dev = affine(p1["flops"], p2["flops"])
    bytes_dev = affine(p1["bytes"], p2["bytes"])
    coll = {k: affine(p1["coll"][k], p2["coll"][k]) for k in p1["coll"]}
    coll_dev = float(sum(coll.values()))

    # roofline terms (single-pod table uses per-device quantities; DESIGN §7)
    compute_s = flops_dev / PEAK_FLOPS_BF16
    memory_s = bytes_dev / HBM_BW
    collective_s = coll_dev / ICI_BW
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", collective_s), key=lambda t: t[1])[0]

    kind = meta["kind"]
    tokens = meta["global_batch"] * (meta["seq_len"] if kind != "decode" else 1)
    n_params = meta["active_params"] if meta["family"] == "moe" \
        else meta["params"]
    model_flops_global = (6 if kind == "train" else 2) * n_params * tokens
    model_flops_dev = model_flops_global / chips

    result = dict(meta)
    result.update({
        "chips": chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collectives": coll,
        "collective_counts": p2["coll_counts"],
        "memory": _mem_dict(mem),
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "bound_s": max(compute_s, memory_s, collective_s),
        },
        "model_flops_per_device": model_flops_dev,
        "useful_compute_ratio": model_flops_dev / flops_dev if flops_dev else 0.0,
    })
    if print_analysis:
        print(f"== {arch} / {shape_name} / {result['mesh']} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        print(f"   memory_analysis: {result['memory']}")
        print(f"   flops/dev {flops_dev:.3e}  bytes/dev {bytes_dev:.3e}  "
              f"coll/dev {coll_dev:.3e}")
        r = result["roofline"]
        print(f"   roofline: compute {r['compute_s']*1e3:.2f}ms  "
              f"memory {r['memory_s']*1e3:.2f}ms  "
              f"collective {r['collective_s']*1e3:.2f}ms  -> {r['dominant']}"
              f"  useful={result['useful_compute_ratio']:.2f}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--override", default=None,
                    help="json dict of ModelConfig overrides (perf iteration)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--no-probes", action="store_true",
                    help="gate-only: skip roofline cost probes")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    overrides = json.loads(args.override) if args.override else None

    cells = []
    archs = all_archs() if (args.all or not args.arch) else [args.arch]
    for arch in archs:
        shapes = [s.name for s in get_shapes(arch)] if (args.all or not args.shape) \
            else [args.shape]
        for sh in shapes:
            cells.append((arch, sh))
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    ok = True
    for arch, sh in cells:
        for mp in meshes:
            try:
                res = run_cell(arch, sh, multi_pod=mp, overrides=overrides,
                               probes=not args.no_probes)
            except Exception as e:  # noqa: BLE001
                res = {"arch": arch, "shape": sh,
                       "mesh": "2x16x16" if mp else "16x16",
                       "error": f"{type(e).__name__}: {e}"}
                ok = False
                print(f"== {arch} / {sh} FAILED: {res['error']}",
                      file=sys.stderr)
            tag = f"_{args.tag}" if args.tag else ""
            fname = f"{arch}_{sh}_{res.get('mesh', 'na')}{tag}.json".replace("/", "-")
            with open(os.path.join(args.out, fname), "w") as f:
                json.dump(res, f, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
