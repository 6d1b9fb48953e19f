"""Token sampling: greedy / temperature / top-k / top-p, jit-compiled.

One fused function over the batch — sampling params are per-sequence arrays
so mixed strategies share a single compiled program (no per-request
recompiles, XLA-friendly static shapes). ``spec_sample`` extends the same
filtered distributions to speculative-decode verification with
DETERMINISTIC drafts (prompt-lookup proposals): accept draft ``d`` with
probability ``P(d)``; on rejection sample from the residual ``P`` with
``d`` removed (for a delta-function proposal the standard
speculative-sampling residual ``(p - q)_+`` is exactly that) — the emitted
stream is an exact sample of the target distribution per position.

The gate. The filter costs two full sorts, a softmax and a cumsum over
``[rows, vocab]``; a dispatch whose lanes are all greedy needs none of it.
Both entry points are a ``lax.cond`` on ``jnp.any(temperature > 0)``, read
on the device from the lanes' own ``temperature`` array: with no sampled
lane the program executes the ``argmax`` alone, with one or more it runs
the whole filter for EVERY row of the batch (a mixed batch pays the full
price; its greedy rows still return their ``argmax``). One compiled
program per shape either way, no static flag; results are bit-identical
to the ungated functions for the same key. ``Engine.step_stats
["decode_sampled_dispatches"]`` counts the decode dispatches that take the
sampled branch.

Generation by diffusion over blocks (``llama.denoise_steps``) asks two
things of every row of a block instead of one token of a lane:
``block_candidates`` gives the row's candidate token and the probability
the row's own softmax gives it (behind the same gate: an all-greedy
dispatch computes ``argmax`` and ``exp(max - logsumexp)``, no sort), and
``block_transfer`` says which masked rows a step fixes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: the name of this file's operations on the device's timeline: the last of
#: ``models/llama.py``'s ``MODEL_SCOPES`` (``jax.named_scope``: metadata of
#: the traced operations, no operation of its own)
_scope = jax.named_scope("model.sample")


def pack_sampling_params(temperature, top_k, top_p) -> np.ndarray:
    """The lanes' sampling parameters as ONE host array, so that they cost
    one upload (or none of their own, as columns of a larger one): int32
    ``[rows, 3]`` = (top_k, temperature, top_p), the two floats as their
    bits. ``unpack_sampling_params`` reads them back inside a program."""
    return np.stack(
        [
            np.asarray(top_k, np.int32),
            np.asarray(temperature, np.float32).view(np.int32),
            np.asarray(top_p, np.float32).view(np.int32),
        ],
        axis=1,
    )


def unpack_sampling_params(packed: jnp.ndarray):
    """(temperature, top_k, top_p) of ``pack_sampling_params``' columns."""
    as_f32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.float32)
    return as_f32(packed[:, 1]), packed[:, 0], as_f32(packed[:, 2])


def _filtered_logits(
    logits: jnp.ndarray,  # [rows, vocab] f32
    temperature: jnp.ndarray,  # [rows] f32; 0 = greedy (filter inert)
    top_k: jnp.ndarray,  # [rows] int32; 0 = disabled
    top_p: jnp.ndarray,  # [rows] f32; 1 = disabled
) -> jnp.ndarray:
    """Temperature-scaled logits with top-k/top-p masking (-inf off-support)."""
    vocab = logits.shape[-1]

    # Temperature scaling (guard 0 for the greedy lanes).
    safe_t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = logits / safe_t

    # Top-k mask: keep the k highest logits per row.
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]  # [rows, vocab]
    k = jnp.where(top_k > 0, top_k, vocab).astype(jnp.int32)
    kth_val = jnp.take_along_axis(
        sorted_desc, jnp.clip(k - 1, 0, vocab - 1)[:, None], axis=-1
    )
    masked = jnp.where(scaled >= kth_val, scaled, -jnp.inf)

    # Top-p (nucleus) on the surviving distribution.
    sorted_masked = jnp.sort(masked, axis=-1)[:, ::-1]
    probs_sorted = jax.nn.softmax(sorted_masked, axis=-1)
    cumprobs = jnp.cumsum(probs_sorted, axis=-1)
    # keep tokens while cumulative prob (exclusive) < top_p
    cutoff_mask = (cumprobs - probs_sorted) < top_p[:, None]
    threshold = jnp.min(
        jnp.where(cutoff_mask, sorted_masked, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(masked >= threshold, masked, -jnp.inf)


def _sample_filtered(logits, temperature, top_k, top_p, rng_key):
    """The sampled branch: every row filtered, greedy rows keep argmax."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = _filtered_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(rng_key, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def _sample_greedy(logits, temperature, top_k, top_p, rng_key):
    """The branch of a dispatch in which no lane samples."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@jax.jit
@_scope
def sample_tokens(
    logits: jnp.ndarray,  # [batch, vocab] f32
    temperature: jnp.ndarray,  # [batch] f32; 0 = greedy
    top_k: jnp.ndarray,  # [batch] int32; 0 = disabled
    top_p: jnp.ndarray,  # [batch] f32; 1 = disabled
    rng_key: jax.Array,
    any_sampled: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Returns sampled token ids [batch] int32.

    ``any_sampled`` is the gate's predicate, ``jnp.any(temperature > 0)``,
    for a caller that has it already (``decode_steps`` computes it once
    outside its scan); left out, it is computed here.
    """
    if any_sampled is None:
        any_sampled = jnp.any(temperature > 0)
    return jax.lax.cond(
        any_sampled, _sample_filtered, _sample_greedy,
        logits, temperature, top_k, top_p, rng_key,
    )


@jax.jit
@_scope
def sample_tokens_packed(
    logits: jnp.ndarray,  # [batch, vocab], any float dtype
    packed: jnp.ndarray,  # [batch, 3] int32: ``pack_sampling_params``
    rng_key: jax.Array,
) -> jnp.ndarray:
    """``sample_tokens`` over float32 logits with the lanes' parameters in
    one operand: what a prefill's first tokens are sampled by."""
    temperature, top_k, top_p = unpack_sampling_params(packed)
    return sample_tokens(
        logits.astype(jnp.float32), temperature, top_k, top_p, rng_key
    )


def _spec_filtered(logits, drafts, temperature, top_k, top_p, rng_key):
    """The sampled branch of ``spec_sample``: every row filtered."""
    b, s, vocab = logits.shape
    flat = logits.reshape(b * s, vocab)
    rep = lambda x: jnp.repeat(x, s)
    masked = _filtered_logits(flat, rep(temperature), rep(top_k), rep(top_p))
    greedy = jnp.argmax(flat, axis=-1).astype(jnp.int32)
    d = drafts.reshape(-1).astype(jnp.int32)

    probs = jax.nn.softmax(masked, axis=-1)
    p_draft = jnp.take_along_axis(probs, d[:, None], axis=-1)[:, 0]

    k_u, k_repl, k_free = jax.random.split(rng_key, 3)
    u = jax.random.uniform(k_u, (b * s,))
    sampled_accept = u < p_draft
    accept = jnp.where(rep(temperature) > 0, sampled_accept, d == greedy)

    draft_hot = jax.nn.one_hot(d, vocab, dtype=bool)
    masked_no_draft = jnp.where(draft_hot, -jnp.inf, masked)
    repl_sampled = jax.random.categorical(k_repl, masked_no_draft, axis=-1)
    replacement = jnp.where(
        rep(temperature) > 0, repl_sampled, greedy
    ).astype(jnp.int32)

    free_sampled = jax.random.categorical(k_free, masked, axis=-1)
    free = jnp.where(rep(temperature) > 0, free_sampled, greedy).astype(
        jnp.int32
    )
    return (
        accept.reshape(b, s),
        replacement.reshape(b, s),
        free.reshape(b, s),
    )


def _spec_greedy(logits, drafts, temperature, top_k, top_p, rng_key):
    """The branch of a burst in which no lane samples."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return drafts.astype(jnp.int32) == greedy, greedy, greedy


@jax.jit
@_scope
def spec_sample(
    logits: jnp.ndarray,  # [batch, s, vocab] f32 — verify logits per position
    drafts: jnp.ndarray,  # [batch, s] int32 — proposed token per position
    temperature: jnp.ndarray,  # [batch] f32; 0 = greedy
    top_k: jnp.ndarray,  # [batch] int32
    top_p: jnp.ndarray,  # [batch] f32
    rng_key: jax.Array,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Speculative verification for deterministic drafts.

    Per position ``j`` with filtered target distribution ``P_j``:

    - ``accept[b, j]``: draft accepted — sampled lanes with probability
      ``P_j(draft)``, greedy lanes iff ``draft == argmax``;
    - ``replacement[b, j]``: the token to emit at the FIRST rejection —
      sampled from ``P_j`` with the draft removed and renormalized (the
      ``(p - q)_+`` residual for a delta proposal; never equals the
      draft), greedy lanes the plain argmax;
    - ``free[b, j]``: an unconditioned sample from ``P_j`` — used for the
      bonus position after all drafts accept (and for empty-proposal
      lanes, where position 0 is a plain decode sample).

    The host walks accept[] to the first False per lane; everything after
    is discarded (those positions were scored under a rejected context).
    """
    return jax.lax.cond(
        jnp.any(temperature > 0), _spec_filtered, _spec_greedy,
        logits, drafts, temperature, top_k, top_p, rng_key,
    )


def _block_greedy(logits, temperature, top_k, top_p, rng_key):
    """The branch of a dispatch in which no lane samples: the argmax of
    each row and its probability under the row's softmax, with no sort."""
    top = jnp.max(logits, axis=-1)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), jnp.exp(top - lse)


def _block_filtered(logits, temperature, top_k, top_p, rng_key):
    """The sampled branch: every row filtered; a sampled lane's rows draw
    from their filtered distribution and read the draw's probability under
    it, greedy lanes' rows keep ``_block_greedy``'s."""
    b, s, vocab = logits.shape
    flat = logits.reshape(b * s, vocab)
    rep = lambda x: jnp.repeat(x, s)
    masked = _filtered_logits(flat, rep(temperature), rep(top_k), rep(top_p))
    drawn = jax.random.categorical(rng_key, masked, axis=-1).astype(jnp.int32)
    logp = jax.nn.log_softmax(masked, axis=-1)
    p_drawn = jnp.exp(jnp.take_along_axis(logp, drawn[:, None], axis=-1)[:, 0])
    greedy, p_greedy = _block_greedy(logits, temperature, top_k, top_p, rng_key)
    sampled = (temperature > 0)[:, None]
    return (
        jnp.where(sampled, drawn.reshape(b, s), greedy),
        jnp.where(sampled, p_drawn.reshape(b, s), p_greedy),
    )


@jax.jit
@_scope
def block_candidates(
    logits: jnp.ndarray,  # [batch, rows, vocab] f32 — a block's logits
    temperature: jnp.ndarray,  # [batch] f32; 0 = greedy
    top_k: jnp.ndarray,  # [batch] int32; 0 = disabled
    top_p: jnp.ndarray,  # [batch] f32; 1 = disabled
    rng_key: jax.Array,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per row of a block: (candidate token [batch, rows] int32, its
    probability under the softmax AT THAT ROW [batch, rows] f32). No
    shift: the logits at a masked position are that position's token.
    Greedy lanes: the argmax and ``exp(max - logsumexp)``; ``temperature
    > 0`` lanes: a draw after temperature/top-k/top-p and its probability
    under that filtered distribution. Gated like ``sample_tokens``."""
    return jax.lax.cond(
        jnp.any(temperature > 0), _block_filtered, _block_greedy,
        logits, temperature, top_k, top_p, rng_key,
    )


@_scope
def block_transfer(
    prob: jnp.ndarray,  # [batch, rows] f32 — ``block_candidates``' second
    masked: jnp.ndarray,  # [batch, rows] bool — rows still to fix
    step: jnp.ndarray,  # [batch] int32 — denoising steps this block has had
    steps: jnp.ndarray,  # [batch] int32 — denoising steps a block gets
    threshold: jnp.ndarray,  # [batch] f32 — confidence threshold
) -> jnp.ndarray:
    """Which masked rows this step fixes (``low_confidence_dynamic``):
    with ``n = rows // steps`` (+1 for the first ``rows % steps`` steps)
    rows owed, every masked row whose probability clears ``threshold`` if
    there are at least ``n`` of them, else the ``n`` masked rows of highest
    probability (all of them where fewer are left; ties to the lower
    row). Never a row that is not masked. [batch, rows] bool."""
    rows = masked.shape[1]
    steps = jnp.clip(steps, 1, rows)
    owed = rows // steps + (step < rows % steps).astype(jnp.int32)  # [b]
    conf = jnp.where(masked, prob, -jnp.inf)
    high = masked & (prob > threshold[:, None])
    order = jnp.argsort(-conf, axis=1, stable=True)
    rank = jnp.argsort(order, axis=1)  # 0 = the most confident row
    top = masked & (rank < owed[:, None])
    enough = jnp.sum(high, axis=1) >= owed
    return jnp.where(enough[:, None], high, top)
