"""The paper's streaming speech-enhancement service (Section III-E / IV-A).

Consumes raw audio sample-by-sample (hop-sized chunks), maintains the STFT
analysis window + the TFTNN recurrent state + the overlap-add synthesis tail,
and emits enhanced audio with one hop (16 ms) of algorithmic latency — the
software twin of the ASIC's real-time loop (512-sample window, 128 hop,
8 kHz).

The synthesis side uses weighted overlap-add with the same Hann window; the
COLA normalizer for hop = n_fft/4 is constant once 4 windows overlap, so each
emitted hop is final (no lookahead).

One pure batched ``stream_hop`` is the single implementation of the hop math.
Three consumers share it:

- ``enhance_streaming`` — the offline scan driver (tests, evaluation),
- ``repro.serve.session_server.SessionPool`` — the multi-session server,
  via ``make_stream_hop`` (jit + donated state + per-slot active masking),
- the quantized inference path (``make_stream_hop(..., quant=FP10)``), which
  reuses ``repro.core.quant`` to run weights/activations on the paper's
  deployment grid.

Every per-stream quantity in ``StreamState`` (including the ``wsum`` COLA
normalizer, which depends on how many hops a stream has seen) carries a
leading batch axis, so a server can reset or swap individual slots with
``reset_slots`` while other streams keep running.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.audio.stft import hann
from repro.core.quant import QuantSpec, quantize, quantize_tree
from repro.models import tftnn as tft_mod

Pytree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StreamState:
    analysis: jax.Array  # (B, n_fft) rolling input window
    synthesis: jax.Array  # (B, n_fft) overlap-add accumulator
    wsum: jax.Array  # (B, n_fft) per-stream window-square accumulator
    model: Pytree  # TFTNN recurrent state, leaves (B, ...)


def init_stream(params: Pytree, cfg: tft_mod.TFTConfig, batch: int) -> StreamState:
    """Zeroed streaming state for ``batch`` independent streams.

    Args:
        params: TFTNN parameters (shapes only — used to size the recurrent
            model state).
        cfg: model/front-end config (``n_fft`` fixes the window buffers).
        batch: number of streams; the leading axis of every state leaf.

    Returns:
        A ``StreamState`` whose leaves are all zeros — a stream that has
        seen no audio.
    """
    return StreamState(
        analysis=jnp.zeros((batch, cfg.n_fft)),
        synthesis=jnp.zeros((batch, cfg.n_fft)),
        wsum=jnp.zeros((batch, cfg.n_fft)),
        model=tft_mod.init_stream_state(params, cfg, batch),
    )


def reset_slots(state: StreamState, slot_mask: jax.Array) -> StreamState:
    """Zero the per-stream state of every slot where ``slot_mask`` is True.

    slot_mask: (B,) bool. All ``StreamState`` leaves have a leading batch
    axis, so this is a model-agnostic fresh-stream reset (used by the session
    server on attach).
    """

    def zero(leaf: jax.Array) -> jax.Array:
        m = slot_mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.where(m, jnp.zeros_like(leaf), leaf)

    return jax.tree_util.tree_map(zero, state)


def hop_analysis(
    state: StreamState,
    hop_samples: jax.Array,
    cfg: tft_mod.TFTConfig,
    quant: Optional[QuantSpec] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Front half of the hop: roll the analysis window, window, FFT, quantize.

    Returns ``(analysis, frame_ri)`` — the updated (B, n_fft) rolling window
    and the (B, F, 2) spectral frame entering the model. Shared verbatim by
    ``stream_hop`` and the deploy path's ``stream_hop_fused`` so the two
    backends see bit-identical model inputs.
    """
    n_fft, hop = cfg.n_fft, cfg.hop
    w = hann(n_fft, hop_samples.dtype)
    analysis = jnp.concatenate([state.analysis[:, hop:], hop_samples], axis=1)
    frame = analysis * w
    spec = jnp.fft.rfft(frame, axis=-1)  # (B, F)
    frame_ri = jnp.stack([spec.real, spec.imag], axis=-1)  # (B, F, 2)
    if quant is not None:
        frame_ri = quantize(frame_ri, quant)
    return analysis, frame_ri


def hop_synthesis(
    state: StreamState,
    analysis: jax.Array,
    frame_ri: jax.Array,
    mask: jax.Array,
    model_state: Pytree,
    cfg: tft_mod.TFTConfig,
) -> Tuple[StreamState, jax.Array]:
    """Back half of the hop: apply the complex mask, iFFT, weighted OLA.

    Takes the (possibly quantized) mask the model emitted and produces
    ``(new_state, out)`` exactly as documented on ``stream_hop``. Shared by
    both hop backends — the COLA/wsum invariant lives in ONE place.
    """
    n_fft, hop = cfg.n_fft, cfg.hop
    w = hann(n_fft, frame_ri.dtype)
    a, b = frame_ri[..., 0], frame_ri[..., 1]
    m = 2.0 * jnp.tanh(mask)
    mc, md = m[..., 0], m[..., 1]
    est = (a * mc - b * md) + 1j * (a * md + b * mc)
    y = jnp.fft.irfft(est, n=n_fft, axis=-1) * w

    synthesis = state.synthesis + y
    wsum = state.wsum + (w * w)[None, :]
    out = synthesis[:, :hop] / jnp.maximum(wsum[:, :hop], 1e-8)
    new_state = StreamState(
        analysis=analysis,
        synthesis=jnp.concatenate([synthesis[:, hop:], jnp.zeros_like(synthesis[:, :hop])], axis=1),
        wsum=jnp.concatenate([wsum[:, hop:], jnp.zeros_like(wsum[:, :hop])], axis=1),
        model=model_state,
    )
    return new_state, out


def hop_passthrough(
    state: StreamState,
    hop_samples: jax.Array,
    cfg: tft_mod.TFTConfig,
) -> Tuple[StreamState, jax.Array]:
    """Model-free identity hop: analysis -> synthesis with a unit mask.

    The graceful-brownout floor. Runs the exact analysis front half and the
    exact weighted-OLA back half of ``stream_hop`` but skips the TFTNN
    entirely (``est = spec``), so under terminal overload the server keeps
    emitting *unenhanced* — but real-time, finite, correctly windowed —
    audio instead of going silent. The model's recurrent state is carried
    through untouched: when the brownout lifts, enhancement resumes from
    whatever recurrent context the stream had (same contract as an inactive
    masked slot).

    Signature-compatible with ``stream_hop``'s hop core, so
    ``make_stream_hop(..., passthrough=True)`` reuses the identical
    masking / fused-scan / ingestion-ring plumbing.
    """
    n_fft, hop = cfg.n_fft, cfg.hop
    analysis, frame_ri = hop_analysis(state, hop_samples, cfg)
    w = hann(n_fft, frame_ri.dtype)
    est = frame_ri[..., 0] + 1j * frame_ri[..., 1]
    y = jnp.fft.irfft(est, n=n_fft, axis=-1) * w

    synthesis = state.synthesis + y
    wsum = state.wsum + (w * w)[None, :]
    out = synthesis[:, :hop] / jnp.maximum(wsum[:, :hop], 1e-8)
    new_state = StreamState(
        analysis=analysis,
        synthesis=jnp.concatenate([synthesis[:, hop:], jnp.zeros_like(synthesis[:, :hop])], axis=1),
        wsum=jnp.concatenate([wsum[:, hop:], jnp.zeros_like(wsum[:, :hop])], axis=1),
        model=state.model,
    )
    return new_state, out


def stream_hop(
    params: Pytree,
    cfg: tft_mod.TFTConfig,
    state: StreamState,
    hop_samples: jax.Array,  # (B, hop) new audio
    *,
    quant: Optional[QuantSpec] = None,
) -> Tuple[StreamState, jax.Array]:
    """Push one hop of audio; emit one hop of enhanced audio.

    Pure function — the single implementation of the hop math shared by the
    offline scan, the session server, and the quantized path.

    Args:
        params: TFTNN parameters (pre-quantized by the caller when serving
            on a deployment grid).
        cfg: model/front-end config (``n_fft``, ``hop``).
        state: per-stream state from ``init_stream`` / a previous call.
        hop_samples: (B, hop) new raw audio, one hop per stream.
        quant: optional ``repro.core.quant`` grid (e.g. FP10 or FXP8):
            additionally rounds the spectral features entering the model and
            the mask leaving it — the activation half of the paper's
            Table VI deployment format. Weight quantization is the caller's
            job (``make_stream_hop`` / ``quantize_tree``).

    Returns:
        ``(new_state, out)`` where ``out`` is (B, hop) enhanced audio. Every
        emitted sample is final (COLA normalization by the running ``wsum``
        — no lookahead, exact from the first warm-up hop).

    The step's stages carry ``jax.named_scope`` names: ``analysis``, the
    model's (``tftnn.stream_step``) and ``synthesis``.
    """
    with jax.named_scope("analysis"):
        analysis, frame_ri = hop_analysis(state, hop_samples, cfg, quant)
    model_state, mask = tft_mod.stream_step(params, state.model, frame_ri, cfg)
    with jax.named_scope("synthesis"):
        if quant is not None:
            mask = quantize(mask, quant)
        return hop_synthesis(state, analysis, frame_ri, mask, model_state, cfg)


def make_stream_hop(
    params: Pytree,
    cfg: tft_mod.TFTConfig,
    *,
    quant: Optional[QuantSpec] = None,
    donate: bool = True,
    backend: str = "xla",
    prune_keep: Optional[float] = None,
    prune_axis: Optional[int] = None,
    prune_granularity: Optional[str] = None,
    prune_block: Tuple[int, int] = (8, 8),
    max_hops_per_step: int = 1,
    from_ring: Optional[int] = None,
    prune_meta: Optional[dict] = None,
    passthrough: bool = False,
) -> Callable[..., Tuple[StreamState, jax.Array]]:
    """Build the jit-compiled batched hop step shared by server and benchmarks.

    With ``max_hops_per_step=1`` (default) returns
    ``step(state, hops, active) -> (state, out)`` where

    - ``hops``: (B, hop) one hop of audio per slot (garbage for idle slots),
    - ``active``: (B,) bool — slots where it is False keep their state
      bit-for-bit and emit zeros, so attach/detach churn in other slots can
      never perturb a running stream,
    - the state argument is donated (``donate=True``): the batched recurrent
      state is updated in place, the steady-state memory traffic the paper's
      constant-size-state execution model is about.

    With ``max_hops_per_step=K > 1`` the returned step is the **multi-hop
    fused dispatch** form,
    ``step(state, hops, hop_counts) -> (state, out)`` where

    - ``hops``: (B, K, hop) — up to K staged hops per slot,
    - ``hop_counts``: (B,) int — how many of the K lanes each slot really
      has staged. Iteration k of the internal ``lax.scan`` is live exactly
      for the slots with ``hop_counts > k`` and is masked out — state kept
      bit-for-bit, zeros emitted — otherwise, i.e. a partially-backlogged
      slot is handled exactly like an inactive slot is today,
    - ``out``: (B, K, hop) — lane k is slot b's k-th enhanced hop (zeros
      for lanes past ``hop_counts[b]``).

    One fused call drains up to K hops per session in ONE device dispatch —
    the fixed host->device->host + Python dispatch cost is amortized over K
    hops, the standard streaming-throughput lever — and is BIT-identical to
    driving the K=1 step K times with the per-iteration active masks.

    With ``from_ring=R`` the step reads its input from a **device-resident
    ingestion ring** instead of a freshly staged host buffer:
    ``step(state, ring, starts, active_or_counts) -> (state, out)`` where

    - ``ring``: (B, R, hop) — the pool's persistent per-slot hop ring,
      written incrementally at ``feed()`` time (``SessionPool`` with
      ``ingest_ring=R``); NOT donated, so an in-flight pipelined step can
      keep reading the array a later ``feed`` functionally superseded,
    - ``starts``: (B,) int — each slot's ring read position; the step
      gathers lanes ``(starts[b] + k) % R`` for k < K and then runs the
      IDENTICAL masked/scan hop math as the staged form (the gathered
      values are exact copies of the fed samples, so outputs stay
      bit-identical — ``tests/test_scheduler.py`` proves it under churn).

    A dispatch then ships only two (B,)-int vectors instead of a packed
    (B, K, hop) audio buffer — what makes per-pump re-tuning by the
    adaptive scheduler cheap. ``R >= max_hops_per_step`` is required (the
    gather reads K lanes).

    ``quant`` switches the whole path onto a ``repro.core.quant`` grid:
    weights are pre-quantized here (once), activations per hop inside
    ``stream_hop``.

    ``backend`` selects the hop implementation:

    - ``"xla"`` (default) — the training graph lowered through generic XLA
      ops (``stream_hop``).
    - ``"pallas"`` — the deploy-compiled graph (``repro.serve.deploy``):
      BN folded out, Pallas kernels in the hot spots, weights pre-quantized
      after folding. Same signature, parity-tested against ``"xla"``.

    ``prune_keep`` (with optional ``prune_axis`` — legacy structured — or
    ``prune_granularity``/``prune_block`` — weight/block/unit masks, see
    ``deploy.build_deploy_plan``) materializes dense zero-skipping masks
    for the plan's matmul weights — lossy by design, like the paper's
    deployment pruning; None serves unpruned. Pruning works on **both**
    backends: masks need the deploy-compiled graph, so a pruned
    ``backend="xla"`` step serves the same folded plan through the pure-jnp
    reference kernels (``use_pallas=False``) — what the interpret-mode CI
    leg and the Pareto sweep's xla axis run. The two pruned backends are
    bit-identical under FP10 activation quantization (tests/test_deploy.py).

    ``prune_meta``: optional dict the factory fills with the plan's exact
    ``sparsity`` report and per-weight ``skip_stats`` when pruning is
    active — how ``SessionPool.shard_stats()`` gets its skip-rate counters
    without recompiling anything.

    ``passthrough=True`` builds the graceful-brownout step instead: the
    model-free ``hop_passthrough`` identity hop behind the identical
    masking / fused-scan / ring plumbing. ``quant`` and the pruning knobs
    are ignored (there is no model to quantize or prune) and ``backend``
    only needs to be valid — both backends share the pure-jnp passthrough.
    """
    if max_hops_per_step < 1:
        raise ValueError("max_hops_per_step must be >= 1")
    if from_ring is not None and from_ring < max_hops_per_step:
        raise ValueError(
            f"from_ring depth {from_ring} < max_hops_per_step "
            f"{max_hops_per_step}: the ring gather reads K lanes"
        )
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown backend {backend!r}: expected 'xla' or 'pallas'")
    if passthrough:
        # brownout floor: model-free analysis->synthesis identity hop. No
        # deploy plan, no weight quantization — there is no model to quantize
        # or prune — but the masking / fused-scan / ring plumbing below is
        # shared verbatim, so parking, K>1 fusion, and the ingestion-ring
        # dispatch all keep working at brownout level 3.
        def hop(state: StreamState, hops: jax.Array):
            return hop_passthrough(state, hops, cfg)

    # an EXPLICIT prune_keep (even 1.0) routes xla through the deploy plan:
    # keep=1.0 is the "dense, same folded graph" baseline the pruning Pareto
    # divides by, so it must share the sparse points' compilation path
    elif backend == "pallas" or prune_keep is not None:
        from repro.serve.deploy import build_deploy_plan, stream_hop_fused

        plan = build_deploy_plan(
            params, cfg, quant=quant, prune_keep=prune_keep,
            prune_axis=prune_axis, prune_granularity=prune_granularity,
            prune_block=prune_block, use_pallas=(backend == "pallas"),
        )
        if prune_meta is not None and plan.masks is not None:
            prune_meta.update(
                sparsity=plan.sparsity,
                skip_stats=plan.skip_stats,
                skip_granularity=plan.skip_granularity,
            )

        def hop(state: StreamState, hops: jax.Array):
            return stream_hop_fused(plan, state, hops)

    else:
        if quant is not None and quant.kind != "none":
            params = quantize_tree(params, quant)

        def hop(state: StreamState, hops: jax.Array):
            return stream_hop(params, cfg, state, hops, quant=quant)

    def masked(state: StreamState, hops: jax.Array, active: jax.Array):
        stepped, out = hop(state, hops)

        def merge(new: jax.Array, old: jax.Array) -> jax.Array:
            m = active.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(m, new, old)

        merged = jax.tree_util.tree_map(merge, stepped, state)
        return merged, jnp.where(active[:, None], out, jnp.zeros_like(out))

    if max_hops_per_step == 1:
        step = masked
    else:
        K = max_hops_per_step

        def step(state: StreamState, hops: jax.Array, hop_counts: jax.Array):
            def body(st, x):
                hop_k, k = x
                return masked(st, hop_k, hop_counts > k)

            xs = (jnp.moveaxis(hops, 1, 0), jnp.arange(K))
            # unroll=True is load-bearing: a rolled scan compiles the body in
            # its own while-loop scope where XLA's fusion choices differ from
            # the straight-line K=1 step by ~1 ulp; unrolled, the fused path
            # is BIT-identical to K sequential single-hop steps (the churn
            # harness in tests/test_fused_hops.py proves it on both backends).
            state, outs = jax.lax.scan(body, state, xs, unroll=True)
            return state, jnp.moveaxis(outs, 0, 1)

    if from_ring is not None:
        R, K, staged = from_ring, max_hops_per_step, step

        def step(state: StreamState, ring: jax.Array, starts: jax.Array, lanes: jax.Array):
            idx = (starts[:, None] + jnp.arange(K)) % R  # (B, K) ring lanes
            hops = jnp.take_along_axis(ring, idx[:, :, None], axis=1)
            # the gather is value-exact (no arithmetic on the audio), so the
            # staged step sees bit-identical inputs; the barrier pins the
            # gathered buffer as a unit so XLA cannot re-fuse the hop math
            # with the gather and change its lowering vs the staged form
            hops = jax.lax.optimization_barrier(hops)
            if K == 1:
                hops = hops[:, 0]
            return staged(state, hops, lanes)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def enhance_streaming(
    params: Pytree,
    cfg: tft_mod.TFTConfig,
    wave: jax.Array,
    *,
    quant: Optional[QuantSpec] = None,
) -> jax.Array:
    """Run the full streaming loop over a batch of utterances via scan.

    Args:
        wave: (B, S) raw audio; trailing samples past a whole hop are dropped.
        quant: optional activation grid, as in ``stream_hop`` (weights are
            not quantized here — pre-quantize ``params`` for full PTQ).

    Returns:
        (B, S') enhanced audio, ``S' = (S // hop) * hop`` — bit-comparable to
        driving ``stream_hop`` by hand and equal to ``enhance_offline`` up to
        float error (THE streaming invariant, see ``enhance_offline``).
    """
    B, S = wave.shape
    hop = cfg.hop
    n = S // hop
    hops = wave[:, : n * hop].reshape(B, n, hop).transpose(1, 0, 2)  # (n, B, hop)
    st = init_stream(params, cfg, B)

    def body(s, x):
        return stream_hop(params, cfg, s, x, quant=quant)

    _, outs = jax.lax.scan(body, st, hops)
    return outs.transpose(1, 0, 2).reshape(B, n * hop)


def enhance_offline(params: Pytree, cfg: tft_mod.TFTConfig, wave: jax.Array) -> jax.Array:
    """Offline reference for the streaming loop: framed STFT -> mask -> OLA.

    Frames the signal exactly as the hop loop sees it (zero history of
    ``n_fft - hop`` samples, window ending at sample ``(k+1)*hop``), runs the
    model over the whole utterance at once, and synthesizes by weighted
    overlap-add with the squared-window normalizer. Because every window
    covering output region [k*hop, (k+1)*hop) has index <= k, the streaming
    loop's running ``wsum`` equals the full-accumulation normalizer used here
    — so ``enhance_streaming(x) == enhance_offline(x)`` for every hop,
    including the warm-up, up to float error. That equality is THE streaming
    invariant and is property-tested in tests/test_streaming_se.py.
    """
    B, S = wave.shape
    n_fft, hop = cfg.n_fft, cfg.hop
    n = S // hop
    w = hann(n_fft, wave.dtype)
    x = jnp.pad(wave[:, : n * hop], ((0, 0), (n_fft - hop, 0)))
    starts = jnp.arange(n) * hop
    idx = starts[:, None] + jnp.arange(n_fft)[None, :]  # (T, n_fft)
    frames = x[:, idx] * w  # (B, T, n_fft)
    spec = jnp.fft.rfft(frames, axis=-1)  # (B, T, F)
    spec_ri = jnp.stack([spec.real, spec.imag], axis=-1).transpose(0, 2, 1, 3)  # (B, F, T, 2)

    mask, _ = tft_mod.apply_tft(params, spec_ri, cfg)

    a, b = spec_ri[..., 0], spec_ri[..., 1]
    m = 2.0 * jnp.tanh(mask)
    mc, md = m[..., 0], m[..., 1]
    est = (a * mc - b * md) + 1j * (a * md + b * mc)  # (B, F, T)
    y = jnp.fft.irfft(est.transpose(0, 2, 1), n=n_fft, axis=-1) * w  # (B, T, n_fft)

    out_len = n * hop + n_fft
    flat = y.reshape(-1, n, n_fft)

    def ola(fr):  # fr: (T, n_fft)
        return jnp.zeros((out_len,), fr.dtype).at[idx].add(fr)

    acc = jax.vmap(ola)(flat)
    wsq = jnp.zeros((out_len,), y.dtype).at[idx].add(w * w)
    out = acc / jnp.maximum(wsq, 1e-8)[None, :]
    return out[:, : n * hop].reshape(B, n * hop)
