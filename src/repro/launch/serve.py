"""Serving launcher: continuous-batching generation with the ServeEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_1p7b \
      [--reduced] [--requests 12] [--new-tokens 8] \
      [--max-batch 4] [--page-size 16] [--max-len 256] [--n-pages 0] \
      [--temperature 0.8] [--top-k 40] [--top-p 0.95] \
      [--priority 0,1] [--ttft-slo 0.5] [--tpot-slo 0.1] \
      [--preempt-policy auto] \
      [--shared-prefix-len 0] [--no-share-prefix] [--stream] \
      [--no-partial-prefix] [--prefill-chunk-tokens 0] \
      [--spec-cf 4 --spec-k 4] [--stats] [--mesh 1,2] \
      [--metrics-json metrics.json] [--trace-out trace.json]

Every decode-capable family runs the same paged continuous-batching
engine (batched chunked prefill + refcounted paged state with prefix
sharing/copy-on-write + slot scheduler + per-request sampling): attention
decoders page their KV cache, SSM archs (falcon_mamba_7b) page
recurrent-state snapshots, hybrid (zamba2_1p2b) composes both — all
behind the CacheBackend protocol (repro.serve.cache). ``--spec-cf``
turns on coarse-propagator speculative decoding (repro.serve.spec): the
paper's coarse grid — every cf-th layer, ODE step rescaled — drafts
``--spec-k`` tokens per wave and the full model verifies them in one
call (greedy output is bitwise identical to plain decode). The
scheduler is overload-safe and SLO-aware (docs/scheduling.md):
``--priority`` cycles requests through a priority list (smaller = more
urgent; urgent requests skip ahead and may preempt under pool
pressure), ``--ttft-slo`` / ``--tpot-slo`` attach latency targets
(reported as SLO attainment, never enforced by dropping), and
``--n-pages`` shrinks the page pool to provoke the overload machinery —
an unservable request prints its ``error`` instead of crashing the
run. ``--mesh
dp,tp`` serves mesh-sharded (docs/sharding.md): weights Megatron-TP over
'model', page pools over 'data' (registry.serve_sharding), one jitted
SPMD call per wave — temp-0 output stays token-for-token identical to
single-device decode. On a CPU container the host platform is forced to
dp*tp devices automatically; use --reduced for the big archs.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of queued requests")
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="in-flight decode slots")
    ap.add_argument("--page-size", type=int, default=16,
                    help="state-page size (tokens)")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page-pool size incl. scratch (0 = every slot "
                         "fits max_len; small pools exercise rejection/"
                         "skip-ahead/preemption)")
    ap.add_argument("--priority", default="0",
                    help="comma list cycled over requests, smaller = more "
                         "urgent (e.g. 0,2 alternates urgent/background)")
    ap.add_argument("--ttft-slo", type=float, default=0.0,
                    help="> 0 attaches a time-to-first-token target (s) "
                         "to every request; reported, never enforced")
    ap.add_argument("--tpot-slo", type=float, default=0.0,
                    help="> 0 attaches a per-output-token target (s)")
    ap.add_argument("--preempt-policy", default="auto",
                    choices=["auto", "spill", "recompute", "off"],
                    help="how urgent requests take pages from running "
                         "ones under pressure (docs/scheduling.md)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="", help="restore params from here")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples (any backend)")
    ap.add_argument("--top-k", type=int, default=0, help="0 disables")
    ap.add_argument("--top-p", type=float, default=1.0, help="1 disables")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prepend this many common tokens to every prompt "
                         "(demonstrates prefix sharing)")
    ap.add_argument("--no-share-prefix", action="store_true",
                    help="disable the prefix cache / copy-on-write pages")
    ap.add_argument("--no-partial-prefix", action="store_true",
                    help="disable token-granular partial-page prefix "
                         "sharing (whole-page trie matching only; "
                         "docs/cache-backends.md)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=0,
                    help="> 0 interleaves chunked prefill with decode: "
                         "at most this many prompt tokens ingest per "
                         "scheduler wave, so long prompts never stall "
                         "in-flight decode (docs/scheduling.md); output "
                         "is bitwise identical either way")
    ap.add_argument("--stream", action="store_true",
                    help="stream the first request token-by-token")
    ap.add_argument("--spec-cf", type=int, default=0,
                    help="> 0 enables coarse-propagator speculative "
                         "decoding with this layer-coarsening factor")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify wave")
    ap.add_argument("--stats", action="store_true",
                    help="print the engine's full counter dict (spec "
                         "decode + prefix cache included)")
    ap.add_argument("--metrics-json", default="",
                    help="write the metrics-registry snapshot (counters, "
                         "gauges, histogram p50/p95/p99) as JSON here")
    ap.add_argument("--trace-out", default="",
                    help="write the request-lifecycle trace as Chrome/"
                         "Perfetto trace-event JSON here (open at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--mesh", default="",
                    help="dp,tp — serve mesh-sharded on a (data, model) "
                         "mesh (e.g. --mesh 1,2 for 2-way tensor "
                         "parallelism); forces dp*tp host devices when "
                         "the platform has fewer")
    args = ap.parse_args(argv)

    mesh_shape = None
    if args.mesh:
        from repro.launch.hostdev import force_host_device_count
        dp, tp = (int(x) for x in args.mesh.split(","))
        mesh_shape = (dp, tp)
        # must land before the jax import below touches the backend
        force_host_device_count(dp * tp)

    import jax
    from repro.configs import registry
    from repro.configs.reduce import reduce_config
    from repro.launch.mesh import make_mesh
    from repro.models import transformer
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.spec import SpecConfig
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    mesh = None
    if mesh_shape is not None:
        n = mesh_shape[0] * mesh_shape[1]
        if jax.device_count() < n:
            raise SystemExit(
                f"--mesh {args.mesh} needs {n} devices, have "
                f"{jax.device_count()} (XLA_FLAGS was set too late?)")
        mesh = make_mesh(mesh_shape, ("data", "model"))

    rcfg = registry.get_config(args.arch, "decode_32k")
    if args.reduced:
        rcfg = reduce_config(rcfg)
    params = transformer.init_model(jax.random.PRNGKey(args.seed), rcfg)
    if args.ckpt:
        from repro.train import checkpoint as ckpt_mod
        restored = ckpt_mod.restore(args.ckpt, params, {"step": 0})
        if restored:
            params = restored[0]
            print(f"restored params from step {restored[2]}")

    spec = SpecConfig(cf=args.spec_cf, k=args.spec_k) \
        if args.spec_cf > 0 else None
    engine = ServeEngine(rcfg, params, mesh=mesh, max_len=args.max_len,
                         max_batch=args.max_batch,
                         page_size=args.page_size, n_pages=args.n_pages,
                         share_prefix=not args.no_share_prefix,
                         partial_prefix=not args.no_partial_prefix,
                         prefill_chunk_tokens=args.prefill_chunk_tokens,
                         spec=spec, preempt_policy=args.preempt_policy)
    print(f"engine: paged continuous-batching via "
          f"{type(engine.backend).__name__}"
          + (f" + spec decode (cf={spec.cf}, k={spec.k}, "
             f"{engine.scheduler.spec.n_coarse} coarse layers)"
             if spec else "")
          + (f" on mesh dp{mesh_shape[0]}xtp{mesh_shape[1]} "
             f"({dp * tp} devices)" if mesh is not None else ""))
    rng = np.random.default_rng(args.seed)
    common = rng.integers(0, rcfg.model.vocab_size,
                          size=args.shared_prefix_len).astype(np.int32)
    priorities = [int(p) for p in args.priority.split(",")]
    reqs = [Request(prompt=np.concatenate([common, rng.integers(
                0, rcfg.model.vocab_size,
                size=int(rng.integers(4, 12))).astype(np.int32)]),
                    max_new_tokens=args.new_tokens,
                    temperature=args.temperature, top_k=args.top_k,
                    top_p=args.top_p, seed=int(rng.integers(0, 2**31)),
                    priority=priorities[i % len(priorities)],
                    ttft_target_s=args.ttft_slo or None,
                    tpot_target_s=args.tpot_slo or None)
            for i in range(args.requests)]
    if args.stream:
        first, rest = reqs[0], reqs[1:]
        stream = engine.submit(first, stream=True)
        rest_rids = [engine.submit(r) for r in rest]
        print("request 0 (streamed): ", end="", flush=True)
        for _tok, piece in stream:
            print(piece, end="", flush=True)
        print()
        done = engine.scheduler.run()
        for r, rid in zip(rest, rest_rids, strict=True):
            ServeEngine._finalize(r, done.pop(rid))
        out = [first] + rest
    else:
        out = engine.generate(reqs)
    for i, r in enumerate(out):
        if r.error is not None:
            print(f"request {i}: prompt[{len(r.prompt)}] FAILED: {r.error}")
            continue
        lat = f" ttft={r.ttft_s*1e3:.0f}ms lat={r.latency_s*1e3:.0f}ms" \
            if r.ttft_s is not None else ""
        prio = f" prio={r.priority}" if len(priorities) > 1 else ""
        print(f"request {i}: prompt[{len(r.prompt)}] -> "
              f"{list(map(int, r.output))}{lat}{prio}")
    thr = engine.scheduler.throughput()
    st = engine.scheduler.stats
    print(f"aggregate: prefill {thr['prefill_tok_s']:.1f} tok/s, "
          f"decode {thr['decode_tok_s']:.1f} tok/s "
          f"({thr['decode_steps']:.0f} decode steps, "
          f"{thr['prefill_calls']:.0f} prefill calls)")
    print(f"prefix sharing: {st['shared_tokens']} prompt tokens "
          f"reused, {st['pages_shared']} pages shared, "
          f"{st['pages_allocated']} pages allocated")
    if st["prefix_partial_hits"]:
        print(f"  token-granular: {st['prefix_partial_hits']} partial-"
              f"page hits, {st['prefix_partial_tokens_shared']} tokens "
              f"reused via fork_partial")
    if st["prefill_chunks"]:
        print(f"chunked prefill: {st['prefill_chunks']} ingest waves at "
              f"budget {args.prefill_chunk_tokens} tokens")
    if st["requests_failed"] or st["preemptions"]:
        print(f"overload: {st['requests_rejected']} rejected, "
              f"{st['requests_failed']} failed, "
              f"{st['preemptions']} preemptions "
              f"({st['pages_spilled']} pages spilled, "
              f"{st['pages_restored']} restored, "
              f"{st['preempt_recomputes']} recompute resumes)")
    def _pcts(hist_name):
        h = engine.obs.metrics.histogram(hist_name)
        if h is None or h.count == 0:
            return None
        p = h.percentiles()
        return (f"p50/p95/p99 = {p['p50']*1e3:.0f}/{p['p95']*1e3:.0f}/"
                f"{p['p99']*1e3:.0f} ms")
    ttft_p, tpot_p = _pcts("request.ttft_s"), _pcts("request.tpot_s")
    if ttft_p or tpot_p:
        print("latency percentiles (registry): "
              + " ".join(f"{k} {v}" for k, v in
                         (("ttft", ttft_p), ("tpot", tpot_p)) if v))
    if args.ttft_slo or args.tpot_slo:
        ok = sum(r.slo_met for r in out)
        print(f"SLO attainment: {ok}/{len(out)} requests met "
              f"ttft<={args.ttft_slo or float('inf'):g}s "
              f"tpot<={args.tpot_slo or float('inf'):g}s")
    if spec:
        es = engine.stats
        print(f"spec decode: {es['tokens_accepted']}/"
              f"{es['tokens_drafted']} drafted tokens accepted "
              f"({100 * es['accept_rate']:.0f}%), "
              f"{es['draft_calls']} draft calls, "
              f"{es['verify_calls']} verify waves")
    if args.stats:
        print("engine stats:")
        for key, val in sorted(engine.stats.items()):
            print(f"  {key} = {val:.4f}" if isinstance(val, float)
                  else f"  {key} = {val}")
    if args.metrics_json:
        import json
        with open(args.metrics_json, "w") as f:
            json.dump(engine.metrics_snapshot(), f, indent=2,
                      default=float)
        print(f"metrics snapshot -> {args.metrics_json}")
    if args.trace_out:
        n = engine.save_trace(args.trace_out)
        print(f"lifecycle trace -> {args.trace_out} ({n} events; open "
              f"at https://ui.perfetto.dev)")
    print(f"steady-state decode probe: "
          f"{engine.throughput_probe(args.max_batch):.1f} tok/s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
