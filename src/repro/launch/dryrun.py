import os
# A CPU-only tool: 512 placeholder host devices, and never the chip (the
# --all children inherit both settings through the environment).
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes with 512 placeholder host devices — proving the sharding
config is coherent without hardware — and extract the roofline terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
        --shape train_4k --mesh single --out artifacts/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Each cell runs in-process; the --all driver spawns one subprocess per cell
(compiles are memory-hungry and XLA flags are per-process).
"""

import argparse        # noqa: E402
import json            # noqa: E402
import subprocess      # noqa: E402
import sys             # noqa: E402
import time            # noqa: E402
from pathlib import Path  # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import SHAPES, applicable_shapes, get_config, list_archs  # noqa: E402
from ..models import get_model                       # noqa: E402
from ..optim.schedules import constant_lr            # noqa: E402
from ..parallel.sharding import rules_for_mesh       # noqa: E402
from ..roofline.hlo_parse import analyze_hlo_text    # noqa: E402
from ..train.step import make_train_step             # noqa: E402
from . import specs as S                             # noqa: E402
from .mesh import make_production_mesh               # noqa: E402


def _memory_analysis(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                out[attr] = int(v)
        out["total_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
    except Exception as e:  # CPU backend may not implement everything
        out["error"] = str(e)
    return out


def _cost_analysis(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and
                ("flops" in k or "bytes accessed" == k or "utilization" in k)}
    except Exception as e:
        return {"error": str(e)}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             microbatches: int = 1, overrides: dict | None = None,
             hlo_out: Path | None = None, tuned: bool = False) -> dict:
    cfg = get_config(arch)
    if tuned:
        from .tuned import tuned_overrides
        # act_shard pays for train/prefill (weight-gather vs activation
        # all-reduce); decode steps are cache-read bound and the constraints
        # on (B,1,d) tensors only add resharding — measured 0.5-0.9x.
        want_act = SHAPES[shape_name].kind != "decode"
        merged = {"act_shard": want_act, **tuned_overrides(arch, shape_name,
                                                           mesh_kind)}
        merged.update(overrides or {})
        overrides = merged
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    api = get_model(cfg)
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_dev = mesh.devices.size
    shape_rules = dict(cfg.sharding_overrides)
    if shape.global_batch == 1:
        # long_500k: batch of 1 cannot shard; spread the cached sequence over
        # every mesh axis instead (context parallelism at 500k tokens).
        shape_rules.setdefault("batch", None)
        shape_rules.setdefault("kv_seq",
                               ("pod", "data", "model") if multi
                               else ("data", "model"))
        shape_rules.setdefault("act_heads", "model")
    rules = rules_for_mesh(mesh, shape_rules)

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "devices": int(n_dev), "kind": shape.kind,
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count(),
           "act_shard": cfg.act_shard, "overrides": overrides or {}}
    t0 = time.time()

    from ..parallel.sharding import activation_rules, reset_activation_rules
    tok = activation_rules(rules if cfg.act_shard else None)
    try:
        return _run_cell_inner(cfg, api, mesh, rules, shape, rec, t0,
                               microbatches, hlo_out)
    finally:
        reset_activation_rules(tok)


def _run_cell_inner(cfg, api, mesh, rules, shape, rec, t0, microbatches,
                    hlo_out):
    n_dev = rec["devices"]
    with mesh:
        if shape.kind == "train":
            params, p_shard = S.abstract_params(api, mesh, rules)
            opt, o_shard = S.abstract_opt_state(params, p_shard, mesh)
            batch = S.train_batch_specs(cfg, shape)
            b_shard = S.batch_shardings(cfg, batch, mesh, rules)
            step = make_train_step(api, constant_lr(1e-4),
                                   microbatches=microbatches)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt, batch)
        elif shape.kind == "prefill":
            params, p_shard = S.abstract_params(api, mesh, rules)
            toks, t_shard = S.prefill_token_specs(cfg, shape, mesh, rules)
            fe = None
            fe_shard = None
            if cfg.frontend is not None:
                fe = S.sds((shape.global_batch, cfg.frontend.n_tokens,
                            cfg.frontend.d_frontend), jnp.float32)
                fe_shard = NamedSharding(mesh, P(rules.get("batch"), None, None))

            def prefill_step(p, t, f=None):
                return api.prefill(p, t, shape.seq_len, frontend=f)

            if fe is None:
                jitted = jax.jit(lambda p, t: prefill_step(p, t),
                                 in_shardings=(p_shard, t_shard))
                lowered = jitted.lower(params, toks)
            else:
                jitted = jax.jit(prefill_step,
                                 in_shardings=(p_shard, t_shard, fe_shard))
                lowered = jitted.lower(params, toks, fe)
        else:  # decode
            params, p_shard = S.abstract_params(api, mesh, rules)
            state, st_shard = S.abstract_decode_state(api, shape, mesh, rules)
            toks, t_shard = S.decode_token_specs(cfg, shape, mesh, rules)

            def decode(p, st, t):
                return api.decode_step(p, st, t)

            jitted = jax.jit(decode,
                             in_shardings=(p_shard, st_shard, t_shard),
                             out_shardings=(None, st_shard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params, state, toks)

        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

    mem = _memory_analysis(compiled)
    # On the forced-host platform memory_analysis aggregates across all
    # partitions (verified: whisper train temp / 256 == the per-device f32
    # logits+CE buffer exactly); normalize to per-device.
    if "total_bytes" in mem:
        mem["temp_bytes_per_device"] = mem.get("temp_size_in_bytes", 0) // n_dev
        mem["args_bytes_per_device"] = mem.get("argument_size_in_bytes", 0) // n_dev
        mem["total_bytes_per_device"] = mem["total_bytes"] // n_dev
    rec["memory"] = mem
    rec["xla_cost"] = _cost_analysis(compiled)
    t2 = time.time()
    hlo = compiled.as_text()
    rec["hlo_bytes"] = len(hlo)
    rec["cost"] = analyze_hlo_text(hlo, n_dev)
    rec["analyze_s"] = round(time.time() - t2, 1)
    if hlo_out is not None:
        import gzip
        with gzip.open(hlo_out, "wt") as f:
            f.write(hlo)
    rec["ok"] = True
    return rec


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def all_cells(mesh_kinds: list[str]) -> list[tuple[str, str, str]]:
    cells = []
    for arch in list_archs():
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            for mk in mesh_kinds:
                cells.append((arch, shape, mk))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (hillclimb knobs)")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--tuned", action="store_true",
                    help="apply §Perf tuned overrides (act_shard + tuned.py)")
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        cells = all_cells(kinds)
        failures = 0
        for arch, shape, mk in cells:
            tag = f"{arch}__{shape}__{mk}"
            path = outdir / f"{tag}.json"
            if path.exists() and json.loads(path.read_text()).get("ok"):
                print(f"[skip] {tag} (cached)")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--mesh", mk, "--out", str(outdir)]
            if args.tuned:
                cmd.append("--tuned")
            print(f"[run ] {tag}", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout,
                               env={**os.environ, "PYTHONPATH": "src"})
            if r.returncode != 0:
                failures += 1
                path.write_text(json.dumps({
                    "arch": arch, "shape": shape, "mesh": mk, "ok": False,
                    "error": r.stderr[-4000:]}, indent=1))
                print(f"[FAIL] {tag}\n{r.stderr[-2000:]}")
        print(f"done; failures={failures}")
        return 1 if failures else 0

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mk in kinds:
        tag = f"{args.arch}__{args.shape}__{mk}"
        rec = run_cell(args.arch, args.shape, mk,
                       microbatches=args.microbatches,
                       overrides=overrides or None,
                       hlo_out=outdir / f"{tag}.hlo.gz", tuned=args.tuned)
        path = outdir / f"{tag}.json"
        path.write_text(json.dumps(rec, indent=1))
        mem = rec.get("memory", {})
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "compile_s")}, ))
        print("memory_analysis:", {k: v for k, v in mem.items()})
        print("cost_analysis(xla):", rec.get("xla_cost"))
        print("cost(walker):", {k: v for k, v in rec["cost"].items()
                                if k != "while_trip_counts"})
    return 0


if __name__ == "__main__":
    sys.exit(main())
