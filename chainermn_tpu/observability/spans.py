"""The scope vocabulary, and the two ways a region gets its name.

Every name under which work shows up in a profiler capture is declared
here, once, and entered where the work happens:

* :func:`named_scope` — inside TRACED code.  The name becomes a path
  component of every enclosed op's HLO ``op_name`` metadata
  (``jit(train_step)/fwd-bwd/jvp(TransformerLM)/...``), which the
  compiled program keeps and
  :mod:`~chainermn_tpu.observability.device_trace` joins to the
  capture's op events: device time by scope.  Only names of
  :data:`STEP_PHASES`, :data:`ALLREDUCE_STAGES`,
  :data:`KERNEL_REGIONS` and :data:`MODEL_PARTS` are accepted.
* :func:`annotate` / :func:`span` — on the HOST.  ``annotate("x")`` is a
  bare ``jax.profiler.TraceAnnotation("chainermn:x")``: a region on the
  profiler's own clock, so an idle gap of the device can be put down to
  what the host was doing in it.  With no profiler session it costs about
  a microsecond.  ``span("x")`` is ``annotate("x")`` plus the host-side
  duration published to whichever telemetry sinks are active: the current
  :class:`~chainermn_tpu.observability.reporter.Reporter` (``span/<name>``
  scalar + histogram) and the current
  :class:`~chainermn_tpu.observability.step_log.StepRecorder` (the next
  step row's ``spans`` field).

A host-side duration measures *dispatch + any blocking*: under JAX's
async dispatch a span around a jitted call is NOT device time (a capture
read by ``device_trace`` is).  It is still the right signal for
host-bound stalls (input pipeline, blocking readbacks, compile storms).
"""

from __future__ import annotations

import contextlib
import re
import time

import jax

from chainermn_tpu.observability import reporter as _reporter
from chainermn_tpu.observability import step_log as _step_log

#: The phases of a train step (``optimizers.py``).  They partition the
#: step: every op of the program is traced under exactly one of them
#: (forward and backward need no scope of their own: inside ``fwd-bwd``
#: the path already says ``jvp(...)`` or ``transpose(jvp(...))``).
STEP_PHASES = ("fwd-bwd", "allreduce", "opt-update")

#: Within ``allreduce`` (``CommunicatorBase.allreduce_grad``): bucket
#: packing, one ``grad-stage<s>`` per stage of the overlapped schedule,
#: and the unpack.
ALLREDUCE_STAGES = ("grad-pack", "grad-unpack")
_ALLREDUCE_STAGE = re.compile(r"^grad-stage\d+$")

#: The kernels: the three flash ``pallas_call``s (also their ``name=``),
#: the two fused-CE scans, paged decode attention; a Mamba-2 mixer from
#: its input to its output projection (``models/transformer.py``) and,
#: nested in it, the chunked state-space scan, forward and backward, and
#: the causal convolution with its SiLU (``ops/ssd.py``); a sparse-expert
#: layer from its router to the sum of its routed and shared parts
#: (``models/transformer.py``) and, nested in it, its parts: the router
#: (matmul, sigmoid, top-k, the sort of the pairs by expert), the gather
#: of the held experts' rows and their weighted scatter back, the grouped
#: matmuls of the held experts (``ops/grouped_matmul.py``, forward and
#: backward), the shared expert; a compressed-convolutional-attention
#: mixer from its down-projections to its output projection
#: (``models/transformer.py``: the flash regions nest in it) and, nested
#: in it, the two causal convolutions with the q-k mean and the value's
#: shift (``cca-conv``) and the queries' and keys' L2 norms, the keys'
#: learned scale and the rotation (``cca-rope``); a Gated DeltaNet mixer
#: from its input projections to its output projection (``gdn-mixer``:
#: its convolution is ``ssm-conv``, the same kernels) and, nested in it,
#: the chunked gated delta rule, forward and backward (``gdn-scan``,
#: ``ops/gated_delta.py``); an attention row's QK-norm and rotary
#: positions (``attn-rope``; the row's output gate is ``mixer-gate``
#: below); a Kimi-Delta-Attention mixer from its projections to its
#: output projection (``kda-mixer``: its three convolutions are
#: ``ssm-conv``, the same kernels) and, nested in it, the chunked delta
#: rule under a decay a key channel, forward and backward (``kda-scan``,
#: ``ops/kda.py``: the kernels ``kda-fwd`` and ``kda-bwd``, which make the
#: heads' norms, decay and running sums themselves, and the bfloat16
#: transposes beside them); a latent-attention (MLA) row from its query and
#: latent projections to its output projection (``mla-mixer``, inside the
#: row's ``attn-mixer``: the flash regions and ``attn-rope`` nest in it).
#: The innermost name of THIS tuple (or of an allreduce stage) on an op's
#: path is its region.
KERNEL_REGIONS = (
    "flash-fwd", "flash-bwd-dq", "flash-bwd-dkv", "fused-ce",
    "paged-decode-attn", "mamba-mixer", "ssd-scan", "ssm-conv",
    "moe-layer", "moe-route", "moe-dispatch", "moe-experts", "moe-shared",
    "cca-mixer", "cca-conv", "cca-rope",
    "gdn-mixer", "gdn-scan", "attn-rope",
    "kda-mixer", "kda-scan", "mla-mixer",
)

#: The model's parts (``models/transformer.py``), so that every op of
#: ``fwd-bwd`` has an owner: the table lookup with positions and scaling
#: (``embed``; a flax module called ``embed`` puts the same name on the
#: path), a layer's pre-norms and the final norm (``norm``), the
#: residual's multiplier and add (``residual``), an attention layer from
#: q/k/v to its output projection (``attn-mixer``: the flash regions nest
#: in it as they do in ``cca-mixer``; ``attn-window`` in its place where
#: the row sees through a sliding window, so that a capture tells the two
#: kinds of row apart — ``device_trace``'s ``within`` reading has the
#: flash regions' time under each — and ``attn-blockdiff`` where the
#: row's table trains by block diffusion and the row sees ``[clean ;
#: noisy]`` through that mask), every mixer's dense matrices
#: (``mixer-proj``), a mixer's float32 side (``mixer-gate``) — Mamba-2's
#: ``dt`` softplus, ``-exp(A_log)``, the casts of ``y`` and the gate, ``y *
#: silu(gate)`` and the gated norm; the Gated DeltaNet's ``beta``, ``g``,
#: L2 norms and gated norm; a gated attention row's ``attn *
#: sigmoid(gate)`` and, where the gate is one number a head of a plain
#: row, its projection ``x W_g`` too — and a dense FFN from ``wi`` to ``wo`` (``ffn``).  The region reading does not see these names (a
#: ``mixer-proj`` op inside ``mamba-mixer`` is still region
#: ``mamba-mixer``); the OWNER reading of ``device_trace`` takes the
#: innermost name of both tuples.
MODEL_PARTS = (
    "embed", "norm", "residual", "attn-mixer", "attn-window",
    "attn-blockdiff", "mixer-proj", "mixer-gate", "ffn",
)

#: What the jitted programs compile as (``jit_<name>`` in a capture's
#: ``XLA Modules`` line): the train steps of ``optimizers.py`` and the
#: serving engine's four programs.
PROGRAM_NAMES = (
    "train_step", "train_step_zero", "train_step_zero3",
    "train_step_with_state", "train_step_zero_with_state",
    "train_step_zero3_with_state",
    "prefill_step", "decode_step", "chunk_step", "cow_step",
)

#: Prefix of every host annotation the library emits.
HOST_PREFIX = "chainermn:"

#: Host regions: training (``train_step`` around the jitted call,
#: ``global_batch`` around batch placement, ``evaluate``), and the
#: serving stages — the scheduler iteration's phases, the engine's three
#: parts of a program call, and the tracer's request stages.
HOST_SPANS = (
    "train_step", "global_batch", "evaluate",
    "admit", "prefill", "prefill_chunk", "decode", "sample", "emit",
    "table-build", "dispatch", "readback",
)

#: JAX's own host events around the compile stages, as a capture holds
#: them on its clock (``jax.profiler.annotate_function`` in
#: ``jax/_src/compiler.py`` and ``interpreters/pxla.py``, read off a
#: capture of a compiling step), by the stage of the start-up ledger
#: (``observability/startup.py``) each belongs to.  Tracing has none:
#: it is the host's Python between the call's start and ``lower``.
COMPILE_HOST_EVENTS = {
    "lower_sharding_computation": "lower",
    "backend_compile_and_load": "compile",
    "backend_compile": "compile",
}


def host_label(event_name: str):
    """What an idle gap under this host event is put down to: the
    library's own annotation by its name, a compile-stage event of JAX's
    by its stage, anything else ``None``."""
    if event_name.startswith(HOST_PREFIX):
        return event_name
    return COMPILE_HOST_EVENTS.get(event_name)


#: Inside a flash kernel's region: the block geometry the call was built
#: with and its tile census a head row (``ops.flash_attention``).  Decided
#: at trace time, so it is a record, not a rate: it rides in the path,
#: ``device_trace`` reads it back off the compiled program and shows it
#: beside the region's device time.  Not a region itself.
TILE_FIELDS = ("block_q", "block_k", "live", "visited", "copied")
_TILES = re.compile(
    r"^tiles-q(\d+)-k(\d+)-live(\d+)-visited(\d+)-copied(\d+)$")


def tiles_scope(block_q: int, block_k: int, live: int, visited: int,
                copied: int):
    """Scope component that records a kernel's geometry (TRACED code,
    inside the kernel's :func:`named_scope`)."""
    return jax.named_scope(
        f"tiles-q{block_q}-k{block_k}-live{live}-visited{visited}"
        f"-copied{copied}")


def parse_tiles(part: str):
    """``{field: int}`` of a :func:`tiles_scope` component, else None."""
    m = _TILES.match(part)
    return dict(zip(TILE_FIELDS, map(int, m.groups()))) if m else None


def is_region(name: str) -> bool:
    """Whether ``name`` is a kernel region or an allreduce stage: what
    the region reading of ``device_trace`` takes."""
    return (
        name in ALLREDUCE_STAGES or name in KERNEL_REGIONS
        or bool(_ALLREDUCE_STAGE.match(name))
    )


def is_scope(name: str) -> bool:
    """Whether ``name`` is a device-side scope of the vocabulary."""
    return name in STEP_PHASES or name in MODEL_PARTS or is_region(name)


def telemetry_active() -> bool:
    """True when a Reporter or StepRecorder is installed — the gate
    library call sites use to keep the zero-telemetry hot path free of
    even span bookkeeping."""
    return (
        _reporter.get_reporter() is not None
        or _step_log.current_recorder() is not None
    )


def annotate(name: str):
    """Host region ``chainermn:<name>`` on the profiler's clock (a
    context manager); nothing is measured or published."""
    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)


@contextlib.contextmanager
def span(name: str):
    """Named host-side region: profiler annotation + duration fan-out.

    Exception-safe: the duration is recorded (and the span marked as an
    error) even when the body raises, so a failed request can't leave a
    half-open span behind for the next request on the thread.  The
    exception propagates unchanged.
    """
    t0 = time.perf_counter()
    err = False
    try:
        with annotate(name):
            yield
    except BaseException:
        err = True
        raise
    finally:
        dt = time.perf_counter() - t0
        rep = _reporter.get_reporter()
        if rep is not None:
            rep.observe(f"span/{name}", dt)
            rep.histogram_observe(f"span/{name}", dt)
            if err:
                rep.count(f"span/{name}/errors", 1)
        rec = _step_log.current_recorder()
        if rec is not None:
            rec.add_span(name, dt)
            if err:
                rec.add_span(f"{name}/error", dt)


def named_scope(name: str):
    """Device-side region naming for TRACED code: ``jax.named_scope``
    under a name of the vocabulary.  A name outside it raises — a scope
    nobody reads, or one that silently is not there, is the failure the
    vocabulary exists to end."""
    if not is_scope(name):
        raise ValueError(
            f"{name!r} is not in the scope vocabulary "
            "(observability/spans.py)"
        )
    return jax.named_scope(name)
