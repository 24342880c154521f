"""Serving launcher: the async scheduler over a synthetic corpus.

    PYTHONPATH=src python -m repro.launch.serve --preset splade_like
    PYTHONPATH=src python -m repro.launch.serve --routing table8 --cache 256
    PYTHONPATH=src python -m repro.launch.serve --shards 4 --host-devices 4
    repro-serve --engine kernel --k 100        # installed console script

Requests go through ``repro.serve.AsyncRetrievalScheduler``: mixed-k
micro-batches (``--k-mix`` draws per-request depths), query-length
routing (``--routing table8``; ``--engine``/``--shards`` configure the
single-route policy otherwise), and an LRU response cache (``--cache N``
entries; the workload repeats queries, so hits show up immediately in
the printed stats). ``--shards N`` serves over a one-axis mesh of N
devices and refuses to start with fewer (``--host-devices`` fakes them
on CPU). The process exits non-zero when any request failed.

Observability: ``--metrics-port N`` serves the live registry over HTTP
(``/metrics`` Prometheus text, ``/metrics.json``, ``/traces``; port 0
binds an ephemeral port and prints it); ``--trace`` turns on
per-request span recording and prints the slowest request's trace
after the run; ``--cost-model PATH`` loads a fitted
``obs.cost.CostModel`` (see ``scripts/fit_cost_model.py``) and enables
cost-sorted batch dispatch.

Heavy imports live inside ``main`` so ``cli`` (the ``repro-serve`` entry
point) can fix up ``XLA_FLAGS`` before jax initializes.
"""
import argparse
import os
import sys


def _preparse_host_devices(argv=None) -> None:
    """--host-devices must reach XLA before the backend initializes, i.e.
    before any repro import triggers a jnp array build. Appends to any
    pre-existing XLA_FLAGS; malformed values fall through to argparse; a
    conflicting pre-existing device count wins, with a warning."""
    argv = sys.argv if argv is None else argv
    n = None
    for i, tok in enumerate(argv):
        if tok == "--host-devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif tok.startswith("--host-devices="):
            n = tok.split("=", 1)[1]
    if n is None or not n.isdigit():
        return
    prev = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in prev:
        if f"xla_force_host_platform_device_count={n}" not in prev:
            print(f"# warning: XLA_FLAGS already pins a device count; "
                  f"--host-devices {n} is ignored ({prev})", file=sys.stderr)
        return
    os.environ["XLA_FLAGS"] = (
        f"{prev} --xla_force_host_platform_device_count={n}".strip())


def main() -> None:
    import jax
    import numpy as np

    from repro.core import build_index, twolevel
    from repro.data import make_corpus
    from repro.launch.compile_cache import enable_compile_cache
    from repro.retrieval import SearchRequest, engine_names
    from repro.serve import (AsyncRetrievalScheduler, RetryPolicy,
                             SchedulerConfig, make_shard_mesh,
                             run_workload, single_route, table8_policy)

    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="splade_like")
    ap.add_argument("--docs", type=int, default=16384)
    ap.add_argument("--qps", type=float, default=200.0)
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--beta", type=float, default=0.3)
    ap.add_argument("--k", type=int, default=10,
                    help="retrieval depth per request")
    ap.add_argument("--k-mix", type=int, nargs="*", default=None,
                    help="draw per-request depths from this set "
                         "(mixed-k micro-batching), e.g. --k-mix 10 100")
    ap.add_argument("--engine", default="batched",
                    choices=sorted(set(engine_names()) - {"dense"}),
                    help="retrieval engine for the single-route policy")
    ap.add_argument("--routing", default="none",
                    choices=("none", "table8"),
                    help="query-length routing policy (Table 8)")
    ap.add_argument("--cache", type=int, default=0,
                    help="LRU response-cache entries (0 = off)")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--executors", type=int, default=0,
                    help="executor-pool worker threads, each with its "
                         "own Retriever replica (0 = sync inline "
                         "dispatch, the deterministic default)")
    ap.add_argument("--admission-limit", type=int, default=0,
                    help="bounded admission queue: max pending rows "
                         "(0 = unbounded)")
    ap.add_argument("--admission-policy", default="block",
                    choices=("block", "reject", "shed"),
                    help="what submit() does when the admission queue "
                         "is full")
    ap.add_argument("--aging-ms", type=float, default=0.0,
                    help="priority aging: a queued request gains one "
                         "priority level per this many ms waited "
                         "(0 = strict priority)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline: still-queued requests "
                         "are shed when the budget runs out, and the "
                         "workload reports goodput next to QPS")
    ap.add_argument("--retries", type=int, default=0,
                    help="max execution attempts per batch (0/1 = fail "
                         "on first error); failed batches requeue with "
                         "deterministic exponential backoff")
    ap.add_argument("--hedge", type=float, default=0.0,
                    help="hedge straggler batches after this many ms "
                         "in flight (0 = off; needs --executors >= 2); "
                         "first result wins")
    ap.add_argument("--swap-demo", action="store_true",
                    help="hot-swap demo: rebuild the index mid-stream "
                         "and swap it in behind the two-phase gate, "
                         "then report the generation + cache evictions")
    ap.add_argument("--shards", type=int, default=1,
                    help="partition the index over N tile-range shards "
                         "(implies --engine sharded)")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="fake N host devices (must be set at launch)")
    ap.add_argument("--exchange-every", type=int, default=0,
                    help="all-gather global theta_Gl every E tiles")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics (Prometheus), /metrics.json "
                         "and /traces on this port while the workload "
                         "runs (0 = ephemeral, printed at startup)")
    ap.add_argument("--trace", action="store_true",
                    help="record per-request spans; the slowest "
                         "request's trace prints after the run")
    ap.add_argument("--cost-model", default=None, metavar="PATH",
                    help="load a fitted obs.cost.CostModel (JSON from "
                         "scripts/fit_cost_model.py) and sort batches "
                         "by predicted chunk count")
    args = ap.parse_args()
    if args.shards > len(jax.devices()):
        ap.error(f"--shards {args.shards} needs {args.shards} devices, "
                 f"have {len(jax.devices())} "
                 f"({jax.devices()[0].platform})")
    enable_compile_cache()
    corpus = make_corpus(args.preset, n_docs=args.docs, n_terms=4096,
                         n_queries=64)
    index = build_index(corpus.merged("scaled"), tile_size=1024)
    params = twolevel.fast(beta=args.beta).replace(schedule="impact")

    if args.shards > 1 or args.engine == "sharded":
        if args.routing != "none":
            ap.error("--shards/--engine sharded cannot combine with "
                     "--routing (the sharded engine is a single route); "
                     "drop one of the flags")
        routing = single_route("sharded", n_shards=args.shards,
                               mesh=make_shard_mesh(args.shards),
                               exchange_every=args.exchange_every)
        print(f"# sharded serving: {args.shards} shards (mesh)")
    elif args.routing == "table8":
        # --engine still matters under routing: it serves the long class
        routing = table8_policy(long_engine=args.engine)
        print(f"# routing: table8 (short -> fine chunks, "
              f"long -> {args.engine})")
    else:
        routing = single_route(args.engine)
        print(f"# serving engine: {args.engine}")

    retry = (RetryPolicy(max_attempts=args.retries)
             if args.retries > 1 else None)
    from repro.obs import CostModel, MetricsRegistry, Tracer
    tracer = Tracer() if args.trace else None
    registry = MetricsRegistry()
    cost_model = (CostModel.load(args.cost_model)
                  if args.cost_model else None)
    if cost_model is not None:
        print(f"# cost model: {args.cost_model} "
              f"(r2={cost_model.r2:.3f}, n={cost_model.n_samples}) — "
              f"cost-sorted dispatch on")
    sched = AsyncRetrievalScheduler(
        index, params,
        SchedulerConfig(max_batch=args.max_batch, cache_size=args.cache,
                        executors=args.executors,
                        admission_limit=args.admission_limit,
                        admission_policy=args.admission_policy,
                        aging_ms=args.aging_ms, retry=retry,
                        hedge_ms=args.hedge,
                        tracer=tracer, metrics=registry,
                        cost_model=cost_model,
                        sort_batches_by_cost=cost_model is not None),
        routing=routing)
    server = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer
        server = MetricsServer(registry, tracer,
                               port=args.metrics_port,
                               extra=sched.stats)
        print(f"# metrics: http://127.0.0.1:{server.port}/metrics "
              f"(.json, /traces)")
    rng = np.random.default_rng(0)
    k_pool = args.k_mix if args.k_mix else [args.k]
    reqs = [SearchRequest(terms=corpus.queries[i % 64],
                          weights_b=corpus.q_weights_b[i % 64],
                          weights_l=corpus.q_weights_l[i % 64],
                          k=int(rng.choice(k_pool)),
                          deadline_ms=args.deadline_ms)
            for i in range(args.requests)]
    if args.swap_demo:
        # serve half the stream, hot-swap a rebuilt index, serve the rest
        mid = len(reqs) // 2
        if args.executors > 0:
            sched.start()
        stats = run_workload(sched, reqs[:mid], qps=args.qps)
        gen = sched.swap_index(
            build_index(corpus.merged("scaled"), tile_size=1024))
        print(f"# hot-swap: installed generation {gen} "
              f"(cache evictions: "
              f"{sched.stats()['cache_gen_evictions']})")
        stats = run_workload(sched, reqs[mid:], qps=args.qps)
        if args.executors > 0:
            sched.close()
    elif args.executors > 0:
        print(f"# executor pool: {args.executors} workers "
              f"(warming routing grid...)")
        with sched:
            stats = run_workload(sched, reqs, qps=args.qps)
    else:
        stats = run_workload(sched, reqs, qps=args.qps)
    print(stats)
    if tracer is not None:
        slow = tracer.slowest("request")
        if slow is not None:
            print(f"# slowest request (trace {slow}):")
            for span in tracer.trace(slow):
                print(f"#   {span['name']}: "
                      f"{(span['t_end'] - span['t_start']) * 1e3:.2f}ms "
                      f"{span['attrs']}")
    if server is not None:
        server.close()
    if stats["failed"]:
        sys.exit(f"error: {stats['failed']} of {stats['submitted']} "
                 f"requests failed")


def cli() -> None:
    """`repro-serve` console entry: env fix-up, then the real main."""
    _preparse_host_devices()
    main()


if __name__ == "__main__":
    cli()
