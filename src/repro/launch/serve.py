"""Batched serving launcher: prefill + continuous greedy decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --batch 4 --prompt-len 64 --decode-steps 64 --mesh 1x1

``--dcim-select`` adds the serving-time macro-selection step: the launcher
synthesizes the multi-spec DCIM frontier through the online synthesis
service (one fused, cached pass over the scenario specs, submitted as
typed INTERACTIVE requests), co-designs it against the deployed arch's GEMM
inventory, and reports the macro the workload would be served on.
``--dcim-cache PATH`` points the service at a persistent frontier store,
making the second launch warm (zero engine executions); ``--dcim-profile
PATH`` round-trips the preference-profile artifact through
:func:`repro.serve.select.apply_profile`.

The ``--dcim-*`` flag cluster is one typed posture,
:class:`repro.serve.config.ServeConfig`: ``--dcim-config PATH`` loads it
from a JSON artifact and every explicitly-passed flag overrides the file.

``--dcim-trace PATH`` turns on :mod:`repro.obs` request tracing for the
launch: the selection pass runs through a :class:`repro.service.
ServiceFrontend` (so every request carries real queued -> batched ->
served timestamps) and a Chrome-trace JSON lands at PATH — load it at
``ui.perfetto.dev`` to see the span tree from request admission through
cache tiers to the fused engine pass.  ``--dcim-kernel-profile PATH``
feeds a measured ``scripts/profile_kernels.py --json`` artifact into the
serving roofline (``kernel_fraction`` derate), closing the loop between
profiled pipeline efficiency and the reported tokens/s bound.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from ..configs import get_config, smoke_config
from ..models import get_model
from ..parallel.logical import split_logical
from ..parallel.sharding import rules_for_mesh
from ..runtime import enable_compile_cache
from ..serve import make_decode_step, make_prefill
from ..serve.config import serve_config_from_args
from .mesh import make_host_mesh
from .train import parse_mesh


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=64)
    ap.add_argument("--dcim-config", default=None, metavar="PATH",
                    help="JSON ServeConfig artifact consolidating the "
                         "--dcim-* posture (schema "
                         "syndcim-serve-config/v1); explicit --dcim-* "
                         "flags override the file")
    ap.add_argument("--dcim-select", action="store_true",
                    help="select a DCIM macro for this workload from the "
                         "multi-spec synthesized frontier before serving")
    ap.add_argument("--dcim-macros", type=int, default=None,
                    help="macro-array size assumed for --dcim-select "
                         "(default 256)")
    ap.add_argument("--dcim-pref", default=None, metavar="W,E,A",
                    help="preference weights wallclock,energy,area for "
                         "--dcim-select (e.g. 0.2,0.6,0.2); default: pure "
                         "wallclock")
    ap.add_argument("--dcim-profile", default=None, metavar="PATH",
                    help="JSON preference-profile artifact persisted per "
                         "deployment config: read before --dcim-select "
                         "(profile weights for this arch override "
                         "--dcim-pref) and updated afterwards with the "
                         "weights the selection ran under")
    ap.add_argument("--dcim-cache", default=None, metavar="PATH",
                    help="persistent frontier-cache directory for the "
                         "synthesis service: the first --dcim-select launch "
                         "writes the synthesized scenario frontiers there, "
                         "every later launch serves them with zero engine "
                         "executions")
    ap.add_argument("--dcim-registry", default=None, metavar="PATH",
                    help="fleet-shared artifact-registry root (a directory "
                         "on shared storage): frontiers synthesized by ANY "
                         "host land there, so every other host's "
                         "--dcim-select launch is warm; claim files keep "
                         "concurrent cold launches from synthesizing the "
                         "same spec twice (see scripts/warm_cache.py to "
                         "pre-fill it ahead of a deployment)")
    ap.add_argument("--dcim-trace", default=None, metavar="PATH",
                    help="enable request tracing and write a Chrome-trace "
                         "JSON (ui.perfetto.dev) of the launch: per-request "
                         "queued/batched/served spans, cache-tier probes, "
                         "engine phases, kernel dispatches")
    ap.add_argument("--dcim-trace-sample", type=float, default=None,
                    metavar="F", help="head sampling rate for --dcim-trace "
                                      "in (0, 1] (default 1.0)")
    ap.add_argument("--dcim-kernel-profile", default=None, metavar="PATH",
                    help="kernel-profile artifact from scripts/"
                         "profile_kernels.py --json: its measured pipeline "
                         "efficiency derates the serving roofline "
                         "(kernel_fraction)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dcim = serve_config_from_args(args)
    if dcim.trace is not None:
        from .. import obs
        obs.configure(enabled=True, sample=dcim.trace_sample)
    kernel_fraction = 1.0
    if dcim.kernel_profile is not None:
        from ..kernels.profile import fraction_from_profile_artifact
        kernel_fraction = fraction_from_profile_artifact(
            dcim.kernel_profile)
        print(f"dcim: kernel profile {dcim.kernel_profile}: serving "
              f"roofline derated by measured pipeline efficiency "
              f"{kernel_fraction:.3f}")
    if dcim.select:
        from ..core.dse import gemm_inventory
        from ..serve.select import apply_profile, select_macros
        from ..service import (ArtifactRegistry, FrontierCache,
                               SynthesisService, get_service)
        if dcim.cache is not None or dcim.registry is not None:
            registry = (None if dcim.registry is None
                        else ArtifactRegistry(dcim.registry))
            service = SynthesisService(
                cache=FrontierCache(store_dir=dcim.cache,
                                    registry=registry))
        else:
            service = get_service()
        serve_via = service
        frontend = None
        if dcim.trace is not None:
            # Route the selection pass through the admission frontend so
            # every traced request carries real queued -> batched ->
            # served timestamps (the span boundaries the trace shows).
            from ..service import ServiceFrontend
            frontend = ServiceFrontend(service)
            serve_via = frontend
        sel, _ = apply_profile(
            dcim.profile,
            lambda profile: select_macros({cfg.name: gemm_inventory(cfg)},
                                          n_macros=dcim.macros,
                                          preference=dcim.pref,
                                          profile=profile,
                                          service=serve_via,
                                          kernel_fraction=kernel_fraction))
        if frontend is not None:
            frontend.close()
        if dcim.profile is not None:
            print(f"dcim: preference profile updated: {dcim.profile}")
        cs, ss = service.cache.stats, service.stats
        print(f"dcim: synthesis service "
              f"{'warm' if ss.misses == 0 else 'cold'} "
              f"(hits={cs.hits + cs.disk_hits + cs.shared_hits} "
              f"misses={ss.misses} fused_passes={ss.fused_passes}"
              + (f", cache={dcim.cache}" if dcim.cache else "")
              + ")")
        if dcim.registry is not None:
            rt = service.cache.registry.telemetry()
            print(f"dcim: shared registry {dcim.registry}: "
                  f"{rt['entries']} entries, "
                  f"hits={rt['hits']} misses={rt['misses']} "
                  f"fills={rt['fills']} "
                  f"claims={rt['claims_acquired']}"
                  f"/{ss.claim_waits} waited"
                  f"/{ss.claim_hits} served-by-peer")
        wi = sel.codesign.workloads.index(cfg.name)
        di = sel.assignment[cfg.name]
        est = sel.serving_for(cfg.name)
        applied = sel.preferences_applied[cfg.name]
        print(f"dcim: {len(sel.pool)} frontier candidates from scenarios "
              f"{', '.join(sel.scenarios)}"
              + (f", preference={applied}" if applied else ""))
        print(f"dcim: selected {sel.label_for(cfg.name)} for {cfg.name} "
              f"({dcim.macros} macros, "
              f"eff_tops={sel.codesign.effective_tops[wi, di]:.3f}, "
              f"util={sel.codesign.avg_util[wi, di]:.3f})")
        print(f"dcim: serving roofline {est.tokens_per_s:.1f} tok/s "
              f"({est.bottleneck}-bound: macro {est.t_macro_s * 1e3:.3f} ms "
              f"vs hbm {est.t_hbm_s * 1e3:.3f} ms per "
              f"{est.tokens}-token step)")
    api = get_model(cfg)
    dims, axes = parse_mesh(args.mesh)
    mesh = make_host_mesh(dims, axes)
    rules = rules_for_mesh(mesh, cfg.sharding_overrides)

    params_l = api.init_params(jax.random.PRNGKey(0))
    params, specs = split_logical(params_l, rules)
    params = jax.device_put(params, jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs))

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab,
                                       (args.batch, args.prompt_len)))
    frontend = None
    if cfg.frontend is not None:
        frontend = jnp.asarray(rng.normal(size=(
            args.batch, cfg.frontend.n_tokens, cfg.frontend.d_frontend)),
            jnp.float32)

    cache_len = args.prompt_len + args.decode_steps
    prefill = jax.jit(make_prefill(api, cache_len))
    decode = jax.jit(make_decode_step(api), donate_argnums=(1,))

    with mesh:
        t0 = time.time()
        logits, state = prefill(params, prompts, frontend)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0
        tok = jnp.argmax(logits[:, -1:], axis=-1)
        out = [tok]
        t1 = time.time()
        for _ in range(args.decode_steps - 1):
            logits, state = decode(params, state, tok)
            tok = jnp.argmax(logits[:, -1:], axis=-1)
            out.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t1

    n_tok = args.batch * args.decode_steps
    print(f"arch={cfg.name} mesh={mesh.shape}")
    print(f"prefill: {args.batch}x{args.prompt_len} tokens in "
          f"{t_prefill:.2f}s")
    print(f"decode : {n_tok} tokens in {t_decode:.2f}s "
          f"({n_tok / max(t_decode, 1e-9):.1f} tok/s)")
    print("sample:", np.asarray(jnp.concatenate(out, axis=1))[0, :16])

    if dcim.trace is not None:
        from ..obs import tracer
        from ..obs.export import write_chrome_trace
        n = write_chrome_trace(tracer.drain(), dcim.trace)
        print(f"dcim: trace: {n} span events -> {dcim.trace} "
              f"(load at ui.perfetto.dev or chrome://tracing)")


if __name__ == "__main__":
    main()
