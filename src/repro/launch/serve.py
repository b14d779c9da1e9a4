"""Serving launcher: continuous-batching generation with optional mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b --reduced \
        --devices 8 --mesh 2x4 --slots 4 --ragged --temperature 0.8 --seed 3
"""
import argparse
import os
import time


def serve_max_len(cfg, *, prompt_len: int, bucket: int, new_tokens: int) -> int:
    """KV length one request can occupy: its prompt padded to the bucket,
    its new tokens, the config's prefix embeddings, and a small margin."""
    return prompt_len + bucket + new_tokens + cfg.num_prefix_embeds + 8


def build_engine(params, cfg, *, prompt_len: int, new_tokens: int, slots: int,
                 bucket: int, **engine_kw):
    """The serving engine this launcher drives (``engine_kw``: paged mode)."""
    from repro.serve.engine import Engine

    max_len = serve_max_len(cfg, prompt_len=prompt_len, bucket=bucket, new_tokens=new_tokens)
    return Engine(params, cfg, max_len=max_len, slots=slots, bucket=bucket, **engine_kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default="")
    ap.add_argument("--batch", type=int, default=4, help="request count")
    ap.add_argument("--slots", type=int, default=4, help="concurrent batch slots")
    ap.add_argument("--bucket", type=int, default=8, help="prompt-length shape bucket")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt/new-token lengths across requests")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help=">0 enables per-slot sampled decoding")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed (request i uses seed+i)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache with shared-prefix reuse")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="page-pool size incl. the reserved scrap page "
                         "(0: slots * pages-per-slot + 1)")
    ap.add_argument("--shards", type=int, default=1,
                    help="partition the paged KV pool into this many "
                         "per-shard pools with block slot pinning and "
                         "shard-balanced admission (paged mode)")
    args = ap.parse_args()
    if args.shards > 1 and not args.paged:
        ap.error("--shards requires --paged (per-shard pools shard the page pool)")

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import numpy as np
    from repro.configs.base import get_config
    from repro.dist import sharding as shlib
    from repro.launch.mesh import parse_mesh_arg
    from repro.models import lm
    from repro.serve.engine import GenRequest
    from repro.utils.compile_cache import enable_compilation_cache

    import jax

    enable_compilation_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.batch):
        s0 = args.prompt_len
        nt = args.new_tokens
        if args.ragged:
            s0 = int(rng.integers(max(args.prompt_len // 4, 1), args.prompt_len + 1))
            nt = int(rng.integers(max(args.new_tokens // 4, 1), args.new_tokens + 1))
        reqs.append(GenRequest(
            tokens=rng.integers(0, cfg.vocab_size, (s0,)).astype(np.int32),
            max_new_tokens=nt, temperature=args.temperature, seed=args.seed + i,
        ))
    max_len = serve_max_len(cfg, prompt_len=args.prompt_len, bucket=args.bucket,
                            new_tokens=args.new_tokens)

    paged_kw = {}
    if args.paged:
        if args.page_size % args.bucket != 0 and args.bucket > 1:
            ap.error(
                f"--page-size {args.page_size} must be a multiple of "
                f"--bucket {args.bucket}: shared-prefix hits are only "
                "bitwise-exact within one padded length, so page and "
                "bucket boundaries must agree"
            )
        # worst-case pages one request can occupy, from the CLI's own
        # request-shaping knobs — the same arithmetic the engine enforces
        # per request at serve() time
        worst = max_len
        pages_per_req = -(-worst // args.page_size)
        if args.pool_pages:
            if args.shards > 1:
                # per-shard pools each reserve their own scrap page, and a
                # request draws only from its slot's shard
                per = -(-args.pool_pages // args.shards)
                cap = args.shards * ((per - 1) // pages_per_req)
            else:
                cap = (args.pool_pages - 1) // pages_per_req
            if cap < 1:
                ap.error(
                    f"--pool-pages {args.pool_pages} cannot hold even one "
                    f"request (worst case {pages_per_req} pages of "
                    f"{args.page_size}); need >= {pages_per_req + 1}"
                )
            if args.slots > cap:
                ap.error(
                    f"--slots {args.slots} exceeds the pool's worst-case "
                    f"concurrency {cap} ({args.pool_pages - 1} usable pages "
                    f"/ {pages_per_req} pages per request); lower --slots "
                    "or raise --pool-pages"
                )
        paged_kw = dict(paged=True, page_size=args.page_size,
                        pool_pages=args.pool_pages or None,
                        shards=args.shards)

    def serve():
        eng = build_engine(params, cfg, prompt_len=args.prompt_len,
                           new_tokens=args.new_tokens, slots=args.slots,
                           bucket=args.bucket, **paged_kw)
        t0 = time.perf_counter()
        outs = eng.serve(reqs)
        return eng, outs, time.perf_counter() - t0

    if args.mesh:
        mesh = parse_mesh_arg(args.mesh)
        with shlib.use_mesh_rules(mesh):
            eng, outs, dt = serve()
    else:
        eng, outs, dt = serve()

    st = eng.stats
    gen = st.generated_tokens
    print(f"served {len(reqs)} requests ({gen} new tokens) in {dt*1e3:.1f} ms "
          f"({len(reqs)/dt:.1f} req/s, {gen/dt:,.0f} tok/s)")
    print(f"dispatches: {st.prefill_dispatches} prefill + {st.decode_dispatches} decode "
          f"({st.tokens_per_dispatch:.2f} tok/dispatch)")
    print(f"padding waste: {100*st.padding_frac:.1f}% of prompt tokens "
          f"(bucket={args.bucket})")
    if args.paged:
        print(f"page pool: peak {st.pool_peak_pages}/{eng.pool.capacity} pages "
              f"of {eng.page_size} ({st.peak_active} slots at peak); "
              f"page waste {100*st.page_frac:.1f}%")
        if eng.shards > 1:
            peaks = st.shard_peak_cost or [0.0] * eng.shards
            print(f"shards: {eng.shards} per-shard pools, peak cost "
                  + " ".join(f"s{i}={c:.0f}" for i, c in enumerate(peaks)))
        print(f"prefix reuse: {st.prefix_hits} warm admissions, "
              f"{st.prefix_hit_tokens} prompt tokens skipped")
    print(f"sample: {outs[0][len(reqs[0].tokens):].tolist()}")


if __name__ == "__main__":
    main()
