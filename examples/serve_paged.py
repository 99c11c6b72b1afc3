"""End-to-end serving driver: a sharded serving session over SMR-managed
paged KV pools + SCOT prefix caches, with concurrent client threads.

    PYTHONPATH=src python examples/serve_paged.py --smr IBR --shards 2 \\
        --eviction lru --requests 12
"""

import argparse

import jax

from repro import api, serving
from repro.configs import get_config
from repro.core.workload import run_serving_workload
from repro.models import build_model


def main():
    ap = argparse.ArgumentParser()
    # every choice list is a registry query — scheme names (NR excluded:
    # it never reclaims, so the page pool would leak dry), traversal
    # policies, and the serving admission/eviction policies
    ap.add_argument("--smr", default="IBR",
                    choices=api.schemes(reclaims=True))
    ap.add_argument("--shards", type=int, default=2,
                    help="independent SMR domains (pool + prefix cache + "
                         "scheme instance per shard)")
    ap.add_argument("--shard-smr", default="per_shard",
                    choices=["per_shard", "shared"],
                    help="per_shard: each shard reclaims independently "
                         "(stall isolation); shared: one scheme instance "
                         "spans all shards")
    ap.add_argument("--admission", default="fifo",
                    choices=api.admission_policies())
    ap.add_argument("--eviction", default="fifo",
                    choices=api.eviction_policies())
    ap.add_argument("--scheduler", default="chunked",
                    choices=api.scheduler_policies(),
                    help="chunked-prefill fairness: 'chunked' bounds how "
                         "long one prompt's ingestion can stall in-flight "
                         "decoders; 'oneshot' is the stall-prone baseline; "
                         "'packed' executes chunked's grants as one "
                         "multi-segment chunk per step")
    ap.add_argument("--backend", default=None,
                    choices=["xla", "pallas", "pallas_interpret"],
                    help="kernel backend for the engine's attention ops "
                         "(default: the platform's — the Pallas kernels "
                         "on a TPU, xla elsewhere).  'pallas' runs decode "
                         "(split-K paged attention) and packed prefill "
                         "through Mosaic and needs a TPU; "
                         "'pallas_interpret' runs the same kernels in "
                         "interpret mode (correct but slow)")
    ap.add_argument("--chunk-tokens", type=int, default=16,
                    help="per-step prefill token budget (page multiple)")
    ap.add_argument("--long-prompts", type=int, default=2,
                    help="long prompts mixed into the request stream (the "
                         "TTFT/ITL interference workload; 0 disables)")
    ap.add_argument("--prefix-traversal", default=None,
                    choices=api.traversal_policies(),
                    help="prefix-cache bucket traversal policy (default: "
                         "negotiated — SCOT iff the scheme is robust); "
                         "'waitfree' demos the paper's §4 variant on the "
                         "admission path")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--clients", type=int, default=3)
    # fault tolerance (DESIGN.md §14)
    ap.add_argument("--fault", action="append", default=[],
                    metavar="KIND:K=V,...",
                    help="schedule a chaos fault (repeatable), e.g. "
                         "'stall:shard=0,after_done=4,duration_s=2' or "
                         "'crash:shard=1,at_step=200'; kinds: "
                         + ", ".join(api.fault_kinds()))
    ap.add_argument("--watchdog", default="migrate",
                    choices=["migrate", "observe", "off"],
                    help="shard watchdog mode: degraded shards lose their "
                         "router slot and (migrate) their sequences move "
                         "to healthy shards via the SMR-safe handoff")
    ap.add_argument("--timeout-s", type=float, default=None,
                    help="per-request deadline (expired requests are "
                         "cancelled through the normal cancel path)")
    ap.add_argument("--pace-s", type=float, default=0.0,
                    help="per-client gap between submissions — stretches "
                         "the run so mid-run faults land under live "
                         "traffic")
    # host swap tier + priority preemption (DESIGN.md §15)
    ap.add_argument("--swap-bytes", type=int, default=0,
                    help="per-shard host swap arena bytes (0 disables); "
                         "with --eviction swap, admission pressure "
                         "preempts lower-priority active sequences into "
                         "the arena and resumes them bit-identically")
    ap.add_argument("--priority-class", action="append", default=[],
                    metavar="NAME:K=V,...",
                    help="define a priority class (repeatable), e.g. "
                         "'interactive:priority=10,ttft_slo_s=2' or "
                         "'batch:priority=0'; requests cycle through the "
                         "defined classes")
    args = ap.parse_args()

    cfg = get_config("tinyllama-1.1b").reduced().replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(7))

    config = serving.ServingConfig(
        smr=args.smr, num_shards=args.shards, shard_smr=args.shard_smr,
        num_pages=128, page_size=8, max_batch=4, max_seq_len=256,
        admission=args.admission, eviction=args.eviction,
        scheduler=args.scheduler, backend=args.backend,
        prefill_chunk_tokens=args.chunk_tokens,
        prefix_traversal=args.prefix_traversal,
        watchdog=args.watchdog,
        default_timeout_s=args.timeout_s,
        faults=tuple(args.fault) or None,
        swap_bytes=args.swap_bytes,
        priority_classes=tuple(args.priority_class) or None)
    class_names = [serving.parse_priority_class(c).name
                   for c in args.priority_class]
    with serving.serve(model, params, config) as session:
        classes = None
        if class_names:
            # long-prompt inserts change the count, so size the class list
            # to the requests the driver will actually submit
            total = args.requests + args.long_prompts
            classes = [class_names[i % len(class_names)]
                       for i in range(total)]
        res = run_serving_workload(
            session, n_requests=args.requests, clients=args.clients,
            shared_prefix_len=16, tail_len=4,
            distinct_prefixes=max(2, args.shards),
            max_new_tokens=args.max_new, wait_each=True,
            long_prompts=args.long_prompts, long_prompt_len=192,
            pace_s=args.pace_s, priority_classes=classes)
        stats = session.stats()

    print(f"scheme={args.smr} shards={args.shards} "
          f"admission={args.admission} eviction={args.eviction} "
          f"scheduler={args.scheduler}/{args.chunk_tokens}tok "
          f"backend={config.backend} "
          f"requests={res.requests} generated={res.tokens} tokens "
          f"in {res.duration_s:.2f}s ({res.tok_per_s:.1f} tok/s, "
          f"prefix hits={res.prefix_hits}, "
          f"ttft_p99={res.ttft_p99_s * 1e3:.1f}ms, "
          f"itl_p99={res.itl_p99_s * 1e3:.1f}ms)")
    if args.fault or res.migrations or res.failed:
        print(f"faults: migrations={res.migrations} failed={res.failed} "
              f"cancelled={res.cancelled} "
              f"heartbeat_misses={res.heartbeat_misses} "
              f"degraded_steps={res.degraded_steps}")
    if args.swap_bytes or res.preemptions:
        print(f"swap: preemptions={res.preemptions} "
              f"swapped_out={res.swapped_out} pages "
              f"swapped_in={res.swapped_in} pages")
    for name, agg in sorted(res.per_class.items()):
        print(f"  class {name}: requests={agg['requests']} "
              f"completed={agg['completed']} cancelled={agg['cancelled']} "
              f"ttft_p99={agg['ttft_p99_s'] * 1e3:.1f}ms")
    print("totals:", stats["totals"])
    for shard in stats["shards"]:
        pc = shard["prefix_cache"]
        print(f"  shard {shard['shard']}: steps={shard['steps']} "
              f"pool_free={shard['pool']['free']} "
              f"cache(hits={pc['hits']} entries={pc['entries']} "
              f"eviction={pc['eviction']}) "
              f"smr(retired={shard['smr']['retired']} "
              f"reclaimed={shard['smr']['reclaimed']})")


if __name__ == "__main__":
    main()
