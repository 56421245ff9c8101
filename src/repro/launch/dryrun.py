import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes and extract the roofline inputs.

The lines above MUST run before any other import (jax locks the backend
and device count on first init), which is why this module sets them at
the very top and why nothing else in the repo sets them globally.  The
dry-run is written for 512 forced host devices: ``JAX_PLATFORMS=cpu``
keeps it (and every ``--sweep`` child, which inherits the environment)
off an attached accelerator, which only one process may hold.

Per cell this emits JSON:
  flops            — compiled.cost_analysis()["flops"]
  bytes_accessed   — cost_analysis bytes (HBM traffic proxy)
  collectives      — {op: operand_bytes} parsed from the optimized HLO
  memory           — compiled.memory_analysis() per-device byte sizes
  peak_bytes       — argument+output+temp+generated (fits-check)

Usage:
  python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k --mesh multi
  python -m repro.launch.dryrun --sweep          # every cell, subprocesses
"""
import argparse
import json
import math
import re
import subprocess
import sys
from typing import Dict, Optional

import numpy as np


# ---------------------------------------------------------------------------
# per-arch train config + microbatching policy
# ---------------------------------------------------------------------------


def pick_train_config(param_count: int):
    """Optimizer-memory policy by model size (ZeRO-sharded either way)."""
    from repro.configs.base import TrainConfig
    if param_count >= 100e9:
        return TrainConfig(moment_dtype="int8", factored_second_moment=True,
                           accum_dtype="bfloat16")
    if param_count >= 10e9:
        return TrainConfig(moment_dtype="bfloat16", factored_second_moment=True)
    return TrainConfig()


def pick_grad_accum(cfg, shape, mesh) -> int:
    """Smallest power-of-two microbatch count keeping the per-device
    residual-stream carries (layers x B_local x T x D x 2B, the scan
    checkpoints reverse-mode must store) under ~2 GB.  The batch-sharding
    ways come from the active policy (e.g. "fsdp" shards batch over the
    whole mesh) and each microbatch must stay divisible by them."""
    if shape.kind != "train":
        return 1
    from repro.distributed.sharding import POLICIES
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assign = POLICIES[cfg.sharding]["batch"]
    names = (assign,) if isinstance(assign, str) else tuple(assign or ())
    ways = 1
    for n in names:
        if n in sizes and shape.global_batch % (ways * sizes[n]) == 0:
            ways *= sizes[n]
    b_local = max(shape.global_batch // ways, 1)
    layers = cfg.num_layers + cfg.encoder_layers
    seq_assign = POLICIES[cfg.sharding].get("seq")
    seq_ways = sizes.get(seq_assign, 1) if isinstance(seq_assign, str) else 1
    if shape.seq_len % max(seq_ways, 1):
        seq_ways = 1
    carry = b_local * (shape.seq_len // seq_ways) * cfg.d_model * 2 * layers
    budget = 2 * 1024 ** 3
    accum = 1
    while carry / accum > budget and accum < b_local and \
            (shape.global_batch // (accum * 2)) % ways == 0:
        accum *= 2
    return accum


# ---------------------------------------------------------------------------
# lowering one cell
# ---------------------------------------------------------------------------


def build_step(arch: str, shape_name: str, multi_pod: bool,
               policy: Optional[str] = None):
    """-> (jitted fn, example abstract args tuple, mesh, meta dict)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, input_pspecs, input_specs
    from repro.configs.base import SHAPES_BY_NAME
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_production_mesh
    from repro.models.registry import get_model
    from repro.training.train_loop import make_sharded_train_step, state_pspecs

    cfg = get_config(arch)
    if policy:  # §Perf hillclimb: "<policy>[+int8gather][+a2a]"
        parts = policy.split("+")
        for flag in parts[1:]:
            if flag == "int8gather":
                cfg = cfg.replace(moe_gather_dtype="int8")
            elif flag == "a2a":
                cfg = cfg.replace(moe_route="a2a")
        if parts[0]:
            cfg = cfg.replace(sharding=parts[0])
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = cfg.sharding
    model = get_model(cfg)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "multi" if multi_pod else "single",
            "policy": policy,
            "params": model.param_count()}

    if shape.kind == "train":
        import dataclasses
        accum = pick_grad_accum(cfg, shape, mesh)
        meta["grad_accum"] = accum
        tc = dataclasses.replace(pick_train_config(model.param_count()),
                                 grad_accum=accum)
        batch_ps = input_pspecs(cfg, shape, mesh, policy, accum)
        step, ab_state, _ = make_sharded_train_step(
            model, tc, mesh, policy, batch_ps)
        ab_batch = input_specs(cfg, shape, accum)
        return step, (ab_state, ab_batch), mesh, meta

    # serving path
    ab_params = model.abstract_params()
    lg_params = model.logical_axes()
    p_sh = shd.tree_named(
        mesh, shd.tree_pspecs(ab_params, lg_params, mesh, policy))
    ab_batch = input_specs(cfg, shape)
    batch_ps = input_pspecs(cfg, shape, mesh, policy)
    b_sh = {k: shd.named(mesh, v) for k, v in batch_ps.items()}

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            hidden, cache = model.prefill(params, batch, mesh=mesh)
            logits = model.logits(params, hidden[:, -1:, :])
            return logits, cache

        step = jax.jit(prefill_step, in_shardings=(p_sh, b_sh))
        return step, (ab_params, ab_batch), mesh, meta

    # decode: one new token against a seq_len cache
    ab_cache, lg_cache = model.cache_specs(shape.global_batch, shape.seq_len)
    c_sh = shd.tree_named(
        mesh, shd.tree_pspecs(ab_cache, lg_cache, mesh, policy))
    tok_sh = shd.named(mesh, batch_ps["tokens"])

    def serve_step(params, cache, tokens, cache_len):
        return model.decode_step(params, cache, tokens, cache_len, mesh=mesh)

    step = jax.jit(serve_step,
                   in_shardings=(p_sh, c_sh, tok_sh, None),
                   out_shardings=(None, c_sh),
                   donate_argnums=(1,))
    ab_tok = ab_batch["tokens"]
    ab_len = jax.ShapeDtypeStruct((), jnp.int32)
    return step, (ab_params, ab_cache, ab_tok, ab_len), mesh, meta


COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")
_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def parse_collectives(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective op from optimized HLO text."""
    # first pass: instruction name -> output shape bytes
    shapes: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)
    out = {op: 0 for op in COLLECTIVE_OPS}
    counts = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        op = m.group(3)
        base = None
        for c in COLLECTIVE_OPS:
            if op == c or op.startswith(c + "-start") or op == c + "-done":
                base = c
                break
        if base is None or op.endswith("-done"):
            continue
        # operand names inside (...) after the op token
        paren = line[line.find("(", line.find(op)) + 1:]
        depth, cur, args = 1, "", []
        for ch in paren:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    args.append(cur)
                    break
            if depth >= 1:
                cur += ch
        names = [a.strip().lstrip("%") for a in args[0].split(",")] if args else []
        b = 0
        for nm in names:
            nm = nm.split(" ")[0].strip()
            if nm in shapes:
                b += _shape_bytes(shapes[nm])
        if b == 0:  # fallback: output size
            b = _shape_bytes(m.group(2))
        out[base] += b
        counts[base] += 1
    return {"bytes": out, "counts": counts}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             keep_hlo: Optional[str] = None,
             policy: Optional[str] = None) -> Dict:
    import jax
    step, args, mesh, meta = build_step(arch, shape_name, multi_pod, policy)
    with mesh:
        lowered = step.lower(*args)
        compiled = lowered.compile()
    from repro.compat import cost_analysis_dict
    cost = cost_analysis_dict(compiled)
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    if keep_hlo:
        with open(keep_hlo, "w") as f:
            f.write(hlo)
    out = dict(meta)
    out.update({
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": coll["bytes"],
        "collective_counts": coll["counts"],
        "memory": mem_d,
        "n_devices": int(np.prod(mesh.devices.shape)),
    })
    return out


# ---------------------------------------------------------------------------
# sweep driver (subprocess per cell: isolation + memory reclamation)
# ---------------------------------------------------------------------------


def sweep(meshes=("single", "multi"), archs=None, shapes=None,
          out_path="results/dryrun.jsonl", timeout: int = 1800):
    from repro.configs import ARCH_IDS, cells
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    done = set()
    if os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass
    failures = []
    for arch in (archs or ARCH_IDS):
        for shape in cells(arch):
            if shapes and shape.name not in shapes:
                continue
            for mesh_kind in meshes:
                key = (arch, shape.name, mesh_kind)
                if key in done:
                    print(f"[skip] {key}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape.name,
                       "--mesh", mesh_kind, "--append", out_path]
                print(f"[run ] {arch} x {shape.name} x {mesh_kind}",
                      flush=True)
                try:
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=timeout)
                    if r.returncode != 0:
                        failures.append((key, r.stderr[-2000:]))
                        print(f"[FAIL] {key}\n{r.stderr[-2000:]}", flush=True)
                except subprocess.TimeoutExpired:
                    failures.append((key, "timeout"))
                    print(f"[TIME] {key}", flush=True)
    print(f"sweep done; {len(failures)} failures")
    for key, err in failures:
        print("FAILED:", key)
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--append", help="append result JSON to this file")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--keep-hlo")
    ap.add_argument("--policy", help="override the sharding policy (perf)")
    args = ap.parse_args()
    if args.sweep:
        failures = sweep(out_path=args.out)
        sys.exit(1 if failures else 0)
    res = run_cell(args.arch, args.shape, args.mesh == "multi",
                   keep_hlo=args.keep_hlo, policy=args.policy)
    js = json.dumps(res)
    print(js)
    if args.append:
        with open(args.append, "a") as f:
            f.write(js + "\n")


if __name__ == "__main__":
    main()
