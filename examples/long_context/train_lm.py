#!/usr/bin/env python
"""Long-context causal language-model training — the net-new capability the
reference never had (SURVEY §5.7: sequence parallelism ABSENT upstream).

Composable long-context stack, selectable per flag:

* ``--sp none``  + flash attention: one chip holds the whole sequence; the
  Pallas flash kernel (ops.flash_attention) streams KV blocks through VMEM
  with online softmax — O(S) memory, ~18x faster than materialized-logits
  attention at S=8192/bf16 on a v5e-class chip.
* ``--sp ring``: the sequence dimension is sharded over the mesh's
  ``intra`` axis; K/V blocks rotate between chips via ``lax.ppermute``
  (parallel.ring_attention) with the same online-softmax accumulation —
  context length scales with the number of chips.
* ``--sp ulysses``: all-to-all swaps the sharded dimension seq<->heads
  around a local full attention (parallel.ulysses).

Mesh layout: ``inter`` = data parallel, ``intra`` = sequence parallel.
Each batch element's tokens are split into ``intra`` contiguous shards;
``position_offset`` keeps rotary/sinusoidal positions globally correct.

Training signal: synthetic successor sequences (next token = current + 1
mod vocab, random start), so the LM's loss collapses quickly — a
correctness canary, not a benchmark.
"""

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.observability import startup
from chainermn_tpu.ops import make_flash_attention_fn
from chainermn_tpu.parallel.ring_attention import make_ring_attention_fn
from chainermn_tpu.parallel.ulysses import make_ulysses_attention_fn
from chainermn_tpu.utils.profiling import sync


def successor_batch(rng, batch, seq_len, vocab):
    start = rng.randint(0, vocab, size=(batch, 1))
    seq = (start + np.arange(seq_len)[None, :]) % vocab
    return seq.astype(np.int32)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--batchsize", type=int, default=8, help="global batch")
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA/MQA: K/V head count (divides --n-heads; "
                        "1 = MQA; default = MHA).  The flash kernel and "
                        "all --sp modes consume the reduced heads "
                        "natively — ring/zigzag rotate only the reduced "
                        "KV blocks")
    p.add_argument("--d-ff", type=int, default=1024)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--steps-per-epoch", type=int, default=20)
    p.add_argument("--sp", choices=["none", "ring", "zigzag", "ulysses"],
                   default="none",
                   help="sequence parallelism over the 'intra' mesh axis "
                   "(zigzag = load-balanced causal ring, half ring's FLOPs)")
    p.add_argument("--no-flash", action="store_true",
                   help="disable the Pallas flash kernel (sp=none only)")
    p.add_argument("--window", type=int, default=None,
                   help="sliding-window (local) attention size.  --sp "
                        "none: the flash kernel skips whole tiles "
                        "outside the band (O(S*window) compute); ring: "
                        "the global-position block masks carry the band "
                        "across shard boundaries; ulysses: full "
                        "sequence per chip after the head all-to-all.  "
                        "zigzag rejects it (its schedule derives from "
                        "full causality)")
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="bfloat16")
    p.add_argument("--dp", type=int, default=None,
                   help="data-parallel ways (inter axis); rest is sequence")
    p.add_argument("--vocab-tp", action="store_true",
                   help="vocab-parallel (Megatron-style) embedding + "
                        "cross-entropy over the sequence axis: the table "
                        "and the LM-head logits stay sharded V/n per "
                        "device (parallel.sharding.vocab_parallel_*); "
                        "needs --sp != none and vocab %% sp ways == 0")
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable fault tolerance: save/auto-resume via the "
                   "multi-node checkpointer (maybe_load on relaunch)")
    p.add_argument("--checkpoint-every", type=int, default=10,
                   help="save a generation every N steps")
    p.add_argument("--checkpoint-name", default="long_context")
    p.add_argument("--packed", action="store_true",
                   help="packed-sequence training: two documents per row, "
                   "segment ids keep attention inside document boundaries "
                   "through EVERY backend (flash kernel masks, rotating "
                   "ring/zigzag KV ids, ulysses all-gathered ids)")
    p.add_argument("--step-log", default=None, metavar="PATH",
                   help="write a JSONL step-event log (one row a step) "
                        "and, once, after two warm-up steps, a "
                        "device_profile row: four steps captured with the "
                        "profiler and read by observability.device_trace "
                        "(device ms a step by scope); summarize with "
                        "python -m chainermn_tpu.tools.obs summarize PATH")
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator("xla_ici", inter_size=args.dp)
    dp, sp_ways = comm.inter_size, comm.intra_size
    S, B, vocab = args.seq_len, args.batchsize, args.vocab
    dtype = jnp.dtype(args.dtype)

    if args.packed and args.sp == "none" and args.no_flash:
        raise SystemExit(
            "--packed with --sp none needs the flash kernel's segment "
            "masks: drop --no-flash"
        )

    # Packed-sequence training: two documents per row at the S/2
    # boundary.  Row-uniform (S,) segment ids (every row shares the
    # boundary) thread through EVERY attention backend — the flash
    # kernel's segment masks (sp=none), rotating KV ids (ring/zigzag),
    # or the all-gathered ids around the local kernel (ulysses).
    seg_row = (
        jnp.asarray((np.arange(S) >= S // 2).astype(np.int32))
        if args.packed else None
    )

    if args.window is not None and (
        args.sp == "zigzag" or (args.sp == "none" and args.no_flash)
    ):
        raise SystemExit("--window: supported with --sp none (flash "
                         "kernel band), ring (global-position band), or "
                         "ulysses (full sequence after the head "
                         "all-to-all); zigzag's chunk schedule is "
                         "derived from FULL causality and would need "
                         "its own banded block selection")
    if args.sp == "none":
        if args.packed:
            attention_fn = make_flash_attention_fn(
                q_segment_ids=seg_row, window=args.window
            )
        else:
            attention_fn = (
                None if args.no_flash
                else make_flash_attention_fn(window=args.window)
            )
        sp_ways_eff = 1
    elif args.sp == "ring":
        attention_fn = make_ring_attention_fn(
            "intra", segment_ids=seg_row, window=args.window
        )
        sp_ways_eff = sp_ways
    elif args.sp == "zigzag":
        from chainermn_tpu.parallel.ring_attention import (
            make_zigzag_ring_attention_fn,
            zigzag_indices as _zz,
        )

        zz_seg = (
            seg_row[np.asarray(_zz(S, sp_ways))]
            if args.packed else None
        )
        attention_fn = make_zigzag_ring_attention_fn(
            "intra", segment_ids=zz_seg
        )
        sp_ways_eff = sp_ways
    else:
        attention_fn = make_ulysses_attention_fn(
            "intra", segment_ids=seg_row, window=args.window
        )
        sp_ways_eff = sp_ways
    if args.sp != "none" and sp_ways == 1:
        raise SystemExit(
            "sequence parallelism needs intra_size > 1; pass --dp to leave "
            "devices on the intra axis (e.g. --dp 1)"
        )
    if args.vocab_tp:
        if args.sp == "none":
            raise SystemExit("--vocab-tp shards over the sequence axis; "
                             "pick an --sp mode")
        if vocab % sp_ways:
            raise SystemExit(f"--vocab-tp needs vocab ({vocab}) divisible "
                             f"by sp ways ({sp_ways})")
        if args.checkpoint_dir:
            raise SystemExit("--vocab-tp + --checkpoint-dir is not wired "
                             "up in this example yet")
    if S % max(sp_ways_eff, 1):
        raise SystemExit(f"--seq-len {S} must divide by sp ways {sp_ways_eff}")
    if args.sp == "zigzag" and S % (2 * sp_ways):
        raise SystemExit(
            f"--sp zigzag needs --seq-len divisible by 2*sp ways "
            f"({2 * sp_ways}); got {S}"
        )
    if args.sp == "ulysses" and args.n_heads % sp_ways:
        # Only ulysses reshapes heads across the axis; ring/zigzag shard
        # the sequence and accept any head count.
        raise SystemExit("--sp ulysses needs n_heads % sp ways == 0")
    if args.kv_heads is not None:
        if args.n_heads % args.kv_heads:
            raise SystemExit("--kv-heads must divide --n-heads")
        if args.sp == "ulysses" and args.kv_heads % sp_ways:
            raise SystemExit("--sp ulysses needs kv_heads % sp ways == 0")

    model = TransformerLM(
        vocab=vocab, d_model=args.d_model, n_heads=args.n_heads,
        d_ff=args.d_ff, n_layers=args.layers, max_len=S, dtype=dtype,
        attention_fn=attention_fn, n_kv_heads=args.kv_heads,
    )
    S_local = S // max(sp_ways_eff, 1)
    tok0 = jnp.zeros((1, S_local), jnp.int32)
    # Init with a dense twin: parameters don't depend on attention_fn, and
    # the ring/ulysses fns need their mesh axis bound (shard_map) to trace.
    init_model = TransformerLM(
        vocab=vocab, d_model=args.d_model, n_heads=args.n_heads,
        d_ff=args.d_ff, n_layers=args.layers, max_len=S, dtype=dtype,
        attention_fn=None, n_kv_heads=args.kv_heads,
    )
    with startup.phase("weights"):
        params = init_model.init(jax.random.PRNGKey(0), tok0)
        params = jax.tree.map(lambda x: x.astype(jnp.float32), params)

    opt = optax.adamw(args.lr, weight_decay=0.01)
    opt_state = opt.init(params)
    if comm.rank == 0:
        n_params = sum(l.size for l in jax.tree.leaves(params))
        print(f"mesh: data={dp} x seq={sp_ways}; sp={args.sp} "
              f"flash={args.sp == 'none' and not args.no_flash} "
              f"params={n_params/1e6:.1f}M seq_len={S}")

    # Predicted positions: each packed document loses its final token.
    denom = B * (S - 2) if args.packed else B * (S - 1)
    # THE per-document position rule, shared by every path: positions
    # restart at the packing boundary (plain global order otherwise).
    base_pos_np = (
        np.concatenate([np.arange(S // 2)] * 2).astype(np.int32)
        if args.packed else np.arange(S, dtype=np.int32)
    )
    packed_pos = jnp.asarray(base_pos_np) if args.packed else None

    if args.sp == "none":
        # Pure DP path through the reference-shaped optimizer wrapper.
        mn_opt = chainermn_tpu.create_multi_node_optimizer(opt, comm)

        def loss_fn(params, batch):
            tok, tgt, wt = batch
            logits = model.apply(params, tok, position_offset=packed_pos)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            # Local mean over this device's (equal-size) share of the
            # predicted positions; the wrapper pmeans across devices.
            return jnp.sum(ce * wt) / (denom / comm.device_size)

        dp_step = mn_opt.make_train_step(loss_fn, donate=False)
        programs = {"train_step": dp_step}

        def step(carry, batch):
            params, st = carry
            params, st, loss = programs["train_step"](params, st, batch)
            return (params, st), loss

        carry = (params, mn_opt.init(params))
    else:
        def body(params, opt_state, tok_l, tgt_l, wt_l, pos_l):
            def loss_fn(params):
                # Explicit global positions: contiguous arange for
                # ring/ulysses, the zigzag permutation for zigzag — the
                # model indexes its positional table with them, so
                # non-contiguous shard layouts stay correct.
                logits = model.apply(params, tok_l, position_offset=pos_l)
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, tgt_l
                )
                # Sum here, global mean via psum: shards hold different
                # numbers of unmasked positions (the last shard masks the
                # final token), so a plain pmean-of-means would be biased.
                return jnp.sum(ce * wt_l) / denom

            loss, grads = jax.value_and_grad(loss_fn)(params)
            loss = lax.psum(loss, comm.axes)
            grads = jax.tree.map(lambda g: lax.psum(g, comm.axes), grads)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        batch_spec = P("inter", "intra")
        mapped = comm.shard_map(
            body,
            in_specs=(P(), P(), batch_spec, batch_spec, batch_spec,
                      P("intra")),
            out_specs=(P(), P(), P()),
        )
        jitted = jax.jit(mapped)

        if args.sp == "zigzag":
            from chainermn_tpu.parallel.ring_attention import zigzag_indices

            seq_perm = zigzag_indices(S, sp_ways)
        else:
            seq_perm = np.arange(S)
        # Positions index the model's positional table: the shared
        # base_pos_np rule carried through the shard layout permutation.
        positions = jnp.asarray(base_pos_np[seq_perm], jnp.int32)

        if args.vocab_tp:
            # Megatron-style vocab parallelism over the SAME devices as
            # the sequence axis: the embedding table and the LM-head
            # logits live sharded V/n per device; the transformer body
            # stays sequence-parallel.  The head follows Megatron's
            # SP+TP composition: all-gather the final hidden states over
            # the axis, then the vocab-sharded CE merges softmax
            # statistics with pmax/psum — logits never materialize
            # beyond a (chunk, V/n) tile per device.
            from chainermn_tpu.parallel.sharding import (
                gather_seq_for_replicated_head,
                vocab_parallel_cross_entropy,
                vocab_parallel_embed,
            )

            S_loc = S // sp_ways
            emb0 = params["params"]["embed"]["embedding"]
            params_rest = {"params": {
                k: v for k, v in params["params"].items() if k != "embed"
            }}
            st_rest0 = opt.init(params_rest)
            st_emb0 = opt.init(emb0)
            emb_spec = P("intra")
            # Optimizer moments are table-shaped: shard them alongside.
            st_emb_spec = jax.tree.map(
                lambda x: emb_spec if getattr(x, "ndim", 0) == 2 else P(),
                st_emb0,
            )

            def body_vtp(pr, emb, st_r, st_e, tok_f, tgt_f, wt_f, pos_f):
                my = lax.axis_index("intra")

                def loss_fn(pr, emb):
                    # grad_reduce=True: the transformer consumes only
                    # this device's sequence slice, so table cotangents
                    # arrive device-varying and the embed backward must
                    # psum across the axis.
                    x_f = vocab_parallel_embed(
                        tok_f, emb, "intra", True
                    )
                    x_l = lax.dynamic_slice_in_dim(
                        x_f, my * S_loc, S_loc, 1
                    )
                    tok_l = lax.dynamic_slice_in_dim(
                        tok_f, my * S_loc, S_loc, 1
                    )
                    pos_l = lax.dynamic_slice_in_dim(
                        pos_f, my * S_loc, S_loc, 0
                    )
                    h_l = model.apply(
                        pr, tok_l, position_offset=pos_l,
                        return_hidden=True, inputs_embeds=x_l,
                    )
                    # NOT plain lax.all_gather: the CE's gradient is
                    # replicated over intra, so all_gather's reduce-
                    # scatter transpose would inflate every transformer
                    # gradient by sp_ways.  The head-gather's backward
                    # slices instead (see sharding.py).
                    h_f = gather_seq_for_replicated_head(h_l, "intra", 1)
                    labels = jnp.where(wt_f > 0, tgt_f, -1)
                    return vocab_parallel_cross_entropy(
                        h_f, emb, labels, "intra"
                    )

                loss, (g_r, g_e) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1)
                )(pr, emb)
                # Transformer grads: intra devices hold their sequence
                # shard's partials, inter rows per-row grads — psum
                # completes both sums; /dp turns the inter sum into the
                # DP mean (the loss is already a per-row mean).
                g_r = jax.tree.map(
                    lambda g: lax.psum(g, comm.axes) / dp, g_r
                )
                # Embed-shard grads are intra-complete (both custom vjps
                # reduce internally); only the DP mean remains.
                g_e = lax.psum(g_e, "inter") / dp
                up_r, st_r = opt.update(g_r, st_r, pr)
                pr = optax.apply_updates(pr, up_r)
                up_e, st_e = opt.update(g_e, st_e, emb)
                emb = optax.apply_updates(emb, up_e)
                return pr, emb, st_r, st_e, lax.pmean(loss, "inter")

            jitted_vtp = jax.jit(comm.shard_map(
                body_vtp,
                in_specs=(P(), emb_spec, P(), st_emb_spec,
                          P("inter"), P("inter"), P("inter"), P()),
                out_specs=(P(), emb_spec, P(), st_emb_spec, P()),
            ))

            programs = {"train_step": jitted_vtp}

            def step(carry, batch):
                pr, emb, st_r, st_e = carry
                pr, emb, st_r, st_e, loss = programs["train_step"](
                    pr, emb, st_r, st_e, *batch, positions
                )
                return (pr, emb, st_r, st_e), loss

            carry = (params_rest, emb0, st_rest0, st_emb0)
        else:
            programs = {"train_step": jitted}

            def step(carry, batch):
                params, opt_state = carry
                params, opt_state, loss = programs["train_step"](
                    params, opt_state, *batch, positions)
                return (params, opt_state), loss

            carry = (params, opt_state)

    rng = np.random.RandomState(0)
    wt_np = np.ones((B, S), np.float32)
    wt_np[:, -1] = 0.0  # final position has no successor
    if args.packed:
        wt_np[:, S // 2 - 1] = 0.0  # first document's final position
    # Zigzag layout: batches are permuted into shard order on the host;
    # targets/weights ride the same permutation (the loss is a positionwise
    # sum, so it is permutation-invariant as long as all three agree).
    perm = seq_perm if args.sp == "zigzag" else np.arange(S)
    wt = jnp.asarray(wt_np[:, perm])

    # Fault tolerance: relaunching the same command line resumes from the
    # newest consistent generation.  The data stream is an rng sequence,
    # so resume replays (draws and discards) the consumed batches — the
    # restored run sees byte-identical remaining data.
    ckpt = None
    resume_step = gstep = 0
    if args.checkpoint_dir:
        from chainermn_tpu.extensions import create_multi_node_checkpointer
        from chainermn_tpu.global_except_hook import add_hook

        add_hook()
        ckpt = create_multi_node_checkpointer(
            args.checkpoint_name, comm, path=args.checkpoint_dir
        )
        loaded, it = ckpt.maybe_load({"carry": carry})
        if it is not None:
            carry = loaded["carry"]
            resume_step = gstep = it
            if comm.rank == 0:
                print(f"resumed from step {it}")

    # --step-log: one row a step, and one device profile after warm-up.
    # The steps reach their program through ``programs`` so that the
    # capture can note each call's arguments (it lowers the program once
    # more from them for its scope table).
    telemetry = contextlib.ExitStack()
    recorder = cap = None
    trained, profile_after, profile_steps = 0, 2, 4
    if args.step_log:
        from chainermn_tpu import observability as obs

        recorder = telemetry.enter_context(
            obs.StepRecorder(args.step_log, rank=comm.rank)
        )

    def end_capture():
        sync(last)
        programs.update(cap.programs)
        report = cap.stop()  # also the recorder's device_profile row
        if comm.rank == 0:
            row = report["programs"].get("train_step", {})
            print("device profile, ms a step by phase: "
                  f"{row.get('phase_ms')} by region: "
                  f"{row.get('region_ms')} unattributed: "
                  f"{row.get('unattributed_ms')} flash tiles by region "
                  f"(blocks; live/visited/copied a head row): "
                  f"{row.get('region_tiles')}")

    last = float("nan")
    for epoch in range(args.epochs):
        t0, n_tok = time.perf_counter(), 0
        for i in range(args.steps_per_epoch):
            # Draw FIRST (the rng stream position is what resume replays),
            # assemble targets only for steps that actually train.
            if args.packed:
                halves = [
                    successor_batch(rng, B, S // 2, vocab) for _ in range(2)
                ]
            else:
                tok_np = successor_batch(rng, B, S, vocab)
            if epoch * args.steps_per_epoch + i < resume_step:
                continue  # replayed rng draw; already trained pre-crash
            if args.packed:
                # Two independent documents per row; targets roll WITHIN
                # each document (the boundary position is weight-zeroed).
                tok_np = np.concatenate(halves, axis=1)
                tgt_np = np.concatenate(
                    [np.roll(h, -1, axis=1) for h in halves], axis=1
                )
            else:
                tgt_np = np.roll(tok_np, -1, axis=1)
            tok = jnp.asarray(tok_np[:, perm])
            tgt = jnp.asarray(tgt_np[:, perm])
            if recorder is not None and trained == profile_after:
                sync(last)
                cap = obs.device_trace.capture(programs).start()
                programs.update({name: cap[name] for name in programs})
            carry, last = step(carry, (tok, tgt, wt))
            n_tok += B * S
            gstep += 1
            trained += 1
            if recorder is not None:
                recorder.step(step=gstep - 1, items=B * S)
            if cap is not None and trained == profile_after + profile_steps:
                end_capture()
                cap = None
            if ckpt is not None and gstep % args.checkpoint_every == 0:
                ckpt.save({"carry": carry}, gstep, block=False)
        if n_tok:
            sync(last)  # host readback: honest timing on all backends
        dt = time.perf_counter() - t0
        if comm.rank == 0 and n_tok:
            print(
                f"epoch {epoch}: loss {float(last):.4f} "
                f"({n_tok / dt:,.0f} tok/s)"
            )
    if cap is not None:  # the run ended inside the captured steps
        end_capture()
    telemetry.close()
    if ckpt is not None:
        ckpt.wait()
        from chainermn_tpu.utils.native import tree_digest

        if comm.rank == 0:
            print(
                f"final step {gstep} params_digest "
                f"{tree_digest(carry[0]):08x}"
            )
    return float(last)


if __name__ == "__main__":
    from chainermn_tpu.utils.profiling import setup_compilation_cache

    setup_compilation_cache()
    main()
