"""Offline kernel autotuner CLI.

Searches the block-config spaces of the Pallas hot paths (flash
attention fwd/bwd ``block_q``×``block_k``, fused cross-entropy
``chunk``) for a shape family — by default the LM bench shapes — and
persists the measured-best configs in the JSON tune cache that
``flash_attention`` / ``fused_cross_entropy`` consult at trace time
(see ``docs/tuning.md``).

Usage::

    # enumerate the search spaces, no compilation or timing:
    python -m chainermn_tpu.tools.autotune --dry-run

    # tune the default bench shapes on the attached TPU and write the
    # cache (CHAINERMN_TPU_TUNE_CACHE or /tmp/chainermn_tpu/...):
    python -m chainermn_tpu.tools.autotune

    # a custom shape family:
    python -m chainermn_tpu.tools.autotune --seq 8192 --window 1024

Prints one JSON line per tuned kernel (the same records ``bench.py
--autotune`` embeds in its output).  Exit code 2 when asked to time
kernels without a TPU backend (``--allow-cpu`` overrides, for harness
debugging only — CPU timings must never steer TPU configs, which is why
the cache key carries the device kind).
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m chainermn_tpu.tools.autotune",
        description="Search + persist best Pallas kernel configs.",
    )
    ap.add_argument("--dry-run", action="store_true",
                    help="enumerate candidate configs only — no "
                         "compilation, no timing, no cache writes")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even when the cache already holds "
                         "an entry for a key")
    ap.add_argument("--cache-path", default=None,
                    help="tune cache file (default: "
                         "$CHAINERMN_TPU_TUNE_CACHE or the /tmp default)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="median-of-k slope samples per candidate")
    ap.add_argument("--n1", type=int, default=3,
                    help="base iteration count for the timing slope")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="permit timing on a non-TPU backend (debugging "
                         "the harness only)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-candidate progress on stderr")
    # Shape family — defaults mirror bench.py's LM flagship.
    ap.add_argument("--batch", type=int, default=4,
                    help="sequences per chip (bench --lm-batch)")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window width (tunes the banded kernel)")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    # Gradient-allreduce bucket cap (docs/performance.md "Bucketed
    # gradient allreduce").
    ap.add_argument("--allreduce-bucket", action="store_true",
                    help="also tune the gradient-allreduce bucket_bytes "
                         "(communicators/packing.py)")
    ap.add_argument("--ab-communicator", default="xla_ici",
                    help="communicator variant to tune the bucket for")
    ap.add_argument("--ab-total-mb", type=float, default=64.0,
                    help="synthetic gradient tree size in MiB")
    ap.add_argument("--ab-leaves", type=int, default=64,
                    help="synthetic gradient tree leaf count")
    # Backward-overlap schedule (docs/performance.md "Backward-overlapped
    # allreduce") — shares the --ab-* tree-family flags.
    ap.add_argument("--overlap-schedule", action="store_true",
                    help="also tune the backward-overlap schedule "
                         "(stage granularity x bucket_bytes; "
                         "communicators/overlap.py)")
    # Quantized gradient wire (docs/performance.md "Quantized gradient
    # wire") — shares the --ab-* tree-family flags.
    ap.add_argument("--comm-dtype", action="store_true",
                    help="also tune the gradient wire dtype "
                         "(none/int8/fp8 scaled allreduce; "
                         "communicators/quant.py)")
    # Quantized KV pages (docs/serving.md "int8 KV cache").
    ap.add_argument("--kv-dtype", action="store_true",
                    help="also tune the serving KV page dtype "
                         "(none/int8 quantized pages) for the "
                         "--kv-* page geometry")
    ap.add_argument("--kv-pages", type=int, default=512,
                    help="pool pages (bench --serve-blocks)")
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="tokens per page (bench --serve-block-size)")
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="KV heads (default: --heads)")
    ap.add_argument("--kv-batch", type=int, default=8,
                    help="decode rows for the timing probe")
    # Speculative draft source (docs/serving.md "Draft models").
    ap.add_argument("--draft", action="store_true",
                    help="also tune the speculative draft source "
                         "(n-gram vs layer-truncated self-draft) for "
                         "the --draft-* target family")
    ap.add_argument("--draft-layers", type=int, default=8,
                    help="target model depth for the draft search "
                         "(candidate draft depths derive from it)")
    ap.add_argument("--draft-max-len", type=int, default=512,
                    help="serving context budget for the draft probe")
    ap.add_argument("--draft-vocab", type=int, default=8192)
    ap.add_argument("--draft-d-model", type=int, default=1024)
    # Chunked-prefill slice size (docs/serving.md "Chunked prefill").
    ap.add_argument("--prefill-chunk", action="store_true",
                    help="also tune the chunked-prefill slice size "
                         "(0/off vs page-aligned slices) for the "
                         "--kv-page-size x --draft-max-len geometry")
    # Shard-group shape (docs/serving.md "Shard groups").
    ap.add_argument("--serve-group", action="store_true",
                    help="also tune the serving shard-group shape "
                         "(tensor-parallel group size x pipeline "
                         "microbatch depth) for the --draft-* target "
                         "family over the local devices")
    ap.add_argument("--serve-group-batch", type=int, default=4,
                    help="decode batch ceiling for the shard-group "
                         "probe (bounds the pipeline depths tried)")
    # Long-context leg (docs/serving.md "Long-context serving").
    ap.add_argument("--prefill-chunk-long", action="store_true",
                    help="also rerun the slice-size objective at the "
                         "long-context bucket (2x --draft-max-len, "
                         "crossing the seed ladder via lazy bucket "
                         "growth); its own cache key, so base and "
                         "long-context slices tune independently")
    args = ap.parse_args(argv)

    from chainermn_tpu.tuning import (
        TuneCache,
        tune_allreduce_bucket,
        tune_comm_dtype,
        tune_draft,
        tune_kv_dtype,
        tune_lm_shapes,
        tune_overlap_schedule,
        tune_prefill_chunk,
        tune_serve_group,
    )

    log = None if args.quiet else (lambda m: print(m, file=sys.stderr))

    if not args.dry_run:
        import jax

        backend = jax.default_backend()
        if backend != "tpu" and not args.allow_cpu:
            print(json.dumps({
                "error": f"refusing to time kernels on backend "
                         f"{backend!r} — tuned configs are per device "
                         "kind and a CPU measurement would steer "
                         "nothing.  Use --dry-run to inspect the "
                         "search space, or --allow-cpu to override.",
            }))
            return 2

    cache = TuneCache(args.cache_path) if args.cache_path else None
    out = tune_lm_shapes(
        batch=args.batch, seq=args.seq, n_heads=args.heads,
        d_model=args.d_model, vocab=args.vocab, window=args.window,
        dtype=args.dtype, cache=cache, force=args.force,
        dry_run=args.dry_run, n1=args.n1, repeats=args.repeats, log=log,
    )
    for kernel in ("flash", "fused_ce"):
        print(json.dumps({kernel: out[kernel]}))
    if args.allreduce_bucket:
        rec = tune_allreduce_bucket(
            communicator=args.ab_communicator, total_mb=args.ab_total_mb,
            n_leaves=args.ab_leaves, dtype=args.dtype, cache=cache,
            force=args.force, dry_run=args.dry_run, n1=args.n1,
            repeats=args.repeats, log=log,
        )
        print(json.dumps({"allreduce_bucket": rec}))
    if args.overlap_schedule:
        rec = tune_overlap_schedule(
            communicator=args.ab_communicator, total_mb=args.ab_total_mb,
            n_leaves=args.ab_leaves, dtype=args.dtype, cache=cache,
            force=args.force, dry_run=args.dry_run, n1=args.n1,
            repeats=args.repeats, log=log,
        )
        print(json.dumps({"overlap_schedule": rec}))
    if args.comm_dtype:
        rec = tune_comm_dtype(
            communicator=args.ab_communicator, total_mb=args.ab_total_mb,
            n_leaves=args.ab_leaves, dtype=args.dtype, cache=cache,
            force=args.force, dry_run=args.dry_run, n1=args.n1,
            repeats=args.repeats, log=log,
        )
        print(json.dumps({"comm_dtype": rec}))
    if args.kv_dtype:
        n_kv = args.kv_heads if args.kv_heads is not None else args.heads
        rec = tune_kv_dtype(
            n_pages=args.kv_pages, page_size=args.kv_page_size,
            n_kv=n_kv, d_head=args.d_model // args.heads,
            n_heads=args.heads, batch=args.kv_batch, dtype=args.dtype,
            cache=cache, force=args.force, dry_run=args.dry_run,
            n1=args.n1, repeats=args.repeats, log=log,
        )
        print(json.dumps({"kv_dtype": rec}))
    if args.draft:
        rec = tune_draft(
            vocab=args.draft_vocab, d_model=args.draft_d_model,
            n_layers=args.draft_layers, max_len=args.draft_max_len,
            dtype=args.dtype, cache=cache, force=args.force,
            dry_run=args.dry_run, n1=args.n1, repeats=args.repeats,
            log=log,
        )
        print(json.dumps({"draft": rec}))
    if args.prefill_chunk:
        rec = tune_prefill_chunk(
            max_len=args.draft_max_len, block_size=args.kv_page_size,
            vocab=args.draft_vocab, d_model=args.draft_d_model,
            n_layers=args.draft_layers, dtype=args.dtype, cache=cache,
            force=args.force, dry_run=args.dry_run, n1=args.n1,
            repeats=args.repeats, log=log,
        )
        print(json.dumps({"prefill_chunk": rec}))
    if args.serve_group:
        rec = tune_serve_group(
            vocab=args.draft_vocab, d_model=args.draft_d_model,
            n_heads=args.heads, n_layers=args.draft_layers,
            max_len=args.draft_max_len, block_size=args.kv_page_size,
            batch=args.serve_group_batch, dtype=args.dtype,
            cache=cache, force=args.force, dry_run=args.dry_run,
            n1=args.n1, repeats=args.repeats, log=log,
        )
        print(json.dumps({"serve_group": rec}))
    if args.prefill_chunk_long:
        rec = tune_prefill_chunk(
            max_len=args.draft_max_len, block_size=args.kv_page_size,
            vocab=args.draft_vocab, d_model=args.draft_d_model,
            n_layers=args.draft_layers, long_context=True,
            dtype=args.dtype, cache=cache, force=args.force,
            dry_run=args.dry_run, n1=args.n1, repeats=args.repeats,
            log=log,
        )
        print(json.dumps({"prefill_chunk_long": rec}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
