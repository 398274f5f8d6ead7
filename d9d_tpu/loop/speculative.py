"""Speculative decoding: draft proposes, target verifies in one call.

Serving extension over the decode stack (docs/design/generation.md):
a small DRAFT model decodes ``k`` tokens autoregressively, then the
TARGET model scores all of them in ONE multi-token continuation call —
``1 + j`` committed tokens per target call instead of 1, where ``j`` is
the accepted prefix length. The whole round — index rewind, the ``k``
draft steps (a ``lax.scan``), the extra key write, and the verify call
— is ONE jitted program, so the host pays a single dispatch and a
single readback per round rather than re-entering Python per draft
token (the same chunked host-interaction contract as the fused
``ContinuousBatcher`` decode loop). Greedy acceptance (argmax-match) makes the
output BIT-IDENTICAL to target-only greedy decoding — speculation is a
latency optimization, never an approximation; the tests pin
``speculative_generate == generate`` exactly.

Cache mechanics (why this needs no new module support):

- The verify call is an ordinary continuation chunk
  (``d9d_tpu.nn.decode_flags.continuation_chunk``): ``t = 1 + k``
  tokens against the warm slot cache, per-row ``start`` — machinery
  chunked prefill and continuous batching already built.
- REJECTION IS AN INDEX REWIND. Attention decode caches are slot-causal
  (``_decode_slot_mask`` / the flash-decode kernel mask by the write
  index), so keys written for rejected proposals become invisible the
  moment ``cache_index`` rewinds — no buffer surgery. Rows rewind
  independently (per-row ``[B]`` indices).
- Recurrent layers (GatedDeltaNet, Mamba) are REJECTED by contract
  (``NotImplementedError``): their state advances irreversibly through
  every token, so rejected proposals would need per-position state
  checkpoints the layers do not keep. The rule is the one
  ``ContinuousBatcher`` keeps its prefix cache off by
  (``nn/decode_flags.recurrent_leaves``: any per-row cache leaf that is
  neither pageable nor a write index), not a list of leaf names.
  Speculate with attention-family models (dense GQA, Llama, MLA);
  hybrids decode through ``generate``/``ContinuousBatcher``.
"""

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from d9d_tpu.core.types import Array
from d9d_tpu.nn.decode_flags import continuation_chunk
from d9d_tpu.telemetry import tracked_jit


def _assert_rewindable(cache) -> None:
    from d9d_tpu.nn.decode_flags import recurrent_leaves

    stateful = recurrent_leaves(cache)
    if stateful:
        raise NotImplementedError(
            "speculative decoding requires rewindable decode state; "
            "recurrent layers (GatedDeltaNet, Mamba) advance a recurrent "
            "state that cannot roll back past rejected proposals "
            f"(cache leaf {'/'.join(next(iter(stateful)))}). Use "
            "generate() or ContinuousBatcher for hybrid models."
        )


def _set_indices(cache, new_index: Array):
    """Rewind every cache_index leaf to per-row ``new_index [B]``."""
    from d9d_tpu.nn.decode_flags import map_cache_index

    return map_cache_index(cache, lambda _idx: new_index)


def speculative_generate(
    model,
    params: Any,
    draft_model,
    draft_params: Any,
    prompt_ids: Array,
    *,
    max_new_tokens: int,
    speculate_k: int = 4,
    eos_id: Optional[int] = None,
) -> Array:
    """``prompt_ids [B, P]`` → ``[B, max_new_tokens]``, bit-identical to
    ``generate(model, params, prompt_ids, max_new_tokens=...)`` (greedy).

    Both models need ``decode_max_length >= P + max_new_tokens - 1``
    (the draft additionally writes up to ``speculate_k`` speculative
    slots, which rewind — capacity must cover
    ``P + max_new_tokens - 1 + speculate_k`` on both). Each round runs
    as ONE jitted dispatch (rewind + ``speculate_k`` draft steps as a
    ``lax.scan`` + the single verify call) and one host readback; the
    host only runs the accept/commit bookkeeping between rounds —
    Python is re-entered once per round, not once per draft token.
    """
    b, p = prompt_ids.shape
    k = int(speculate_k)
    if k < 1:
        raise ValueError(f"speculate_k must be >= 1, got {k}")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}"
        )
    for name, m in (("model", model), ("draft_model", draft_model)):
        dml = int(getattr(m, "decode_max_length", 0))
        need = p + max_new_tokens - 1 + k
        if dml < need:
            raise ValueError(
                f"{name}.decode_max_length={dml} < prompt {p} + "
                f"max_new_tokens {max_new_tokens} - 1 + speculate_k {k} "
                f"= {need} (speculative slots rewind but must fit)"
            )

    def prefill(m, prm):
        z_pos = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p))
        logits, state = m.apply(
            {"params": prm}, prompt_ids.astype(jnp.int32), z_pos,
            method=m.logits_last, mutable=["cache"],
        )
        return logits[:, -1], state["cache"]

    # contract check BEFORE any forward pass: eval_shape exposes the
    # cache tree (leaf names included) without compiling or running
    z1 = jnp.zeros((b, 1), jnp.int32)
    for m, prm in ((model, params), (draft_model, draft_params)):
        _assert_rewindable(
            jax.eval_shape(m.init, jax.random.PRNGKey(0), z1, z1, z1)[
                "cache"
            ]
        )

    t_logits, t_cache = prefill(model, params)
    d_logits, d_cache = prefill(draft_model, draft_params)
    # per-row committed length (rows accept different prefix lengths);
    # the caches' write indices are NOT touched here — every round's
    # spec_round opens by rewinding both to the committed length, which
    # covers the first round too (nothing reads them in between)
    n = np.full((b,), p, np.int32)

    def draft_step(prm, cache, tok, pos):
        logits, state = draft_model.apply(
            {"params": prm, "cache": cache},
            tok[:, None], pos[:, None],
            method=draft_model.logits_last, mutable=["cache"],
        )
        return (
            state["cache"],
            jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32),
        )

    def round_fn(t_cache, d_cache, pending, n_eff, t_params, d_params):
        """One full speculation round as a single XLA program: rewind both
        caches to the committed length, draft ``k`` greedy tokens with a
        ``lax.scan`` (plus the extra key-write for the fully-accepted
        case), then verify ``pending + proposals`` in one target call —
        the host dispatches ONCE and reads back once per round instead of
        re-entering Python for every draft token.

        Both param trees are TRACED ARGUMENTS, never closure captures: a
        captured tree is baked into the executable as a constant (the
        install_weights publish-recompile class — D9D002)."""
        t_cache = _set_indices(t_cache, n_eff)
        d_cache = _set_indices(d_cache, n_eff)

        def body(carry, i):
            cache, tok = carry
            cache, nxt = draft_step(d_params, cache, tok, n_eff + i)
            return (cache, nxt), nxt

        (d_cache, last), props = jax.lax.scan(
            body, (d_cache, pending), jnp.arange(k, dtype=jnp.int32)
        )
        proposals = jnp.moveaxis(props, 0, 1)  # [B, k]
        # one extra draft step writes proposals[k-1]'s KEY (its output is
        # discarded): on a fully-accepted round the committed text
        # includes proposals[k-1], and without this write the draft
        # cache would carry a permanently visible unwritten slot —
        # silently degrading every later proposal's conditioning (and
        # with it the acceptance rate)
        d_cache, _ = draft_step(d_params, d_cache, last, n_eff + k)
        toks = jnp.concatenate([pending[:, None], proposals], axis=1)
        pos = n_eff[:, None] + jnp.arange(1 + k, dtype=jnp.int32)[None]
        # trace-time flag: the verify chunk attends the warm slot cache
        # (valid at any index), not the empty-cache prefill fast path
        with continuation_chunk():
            logits, state = model.apply(
                {"params": t_params, "cache": t_cache},
                toks, pos, method=model.logits, mutable=["cache"],
            )
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, 1+k]
        return state["cache"], d_cache, proposals, greedy

    spec_round = tracked_jit(
        round_fn, name="serve/spec_round", donate_argnums=(0, 1)
    )

    # first committed token: target's own greedy continuation of the
    # prompt (not yet fed to either cache)
    pending = np.asarray(jnp.argmax(t_logits, axis=-1), np.int32)
    out = np.zeros((b, max_new_tokens), np.int32)
    out[:, 0] = pending
    emitted = np.ones((b,), np.int32)
    done = (
        (pending == eos_id) if eos_id is not None
        else np.zeros((b,), bool)
    )

    while int((emitted < max_new_tokens).sum()) and not bool(done.all()):
        # done rows still flow through the static-shape step; park their
        # writes at slot 0 (their cache is dead) so a finished row near
        # capacity can never violate the overflow contract
        n_eff = np.where(done, 0, n).astype(np.int32)
        # ONE dispatch per round: rewind-to-committed + k draft steps +
        # the extra key write + the verify call, all inside spec_round;
        # ONE readback fetches proposals and the target's greedy tokens
        t_cache, d_cache, proposals_d, greedy_d = spec_round(
            t_cache, d_cache, jnp.asarray(pending), jnp.asarray(n_eff),
            params, draft_params,
        )
        # d9d-lint: disable=D9D003 — the one accounted readback per round
        proposals, greedy = jax.device_get((proposals_d, greedy_d))
        # greedy[:, i] = target tok after toks[:, :i+1]

        # --- accept the matching prefix, commit the bonus token -------
        new_tokens = np.zeros((b,), np.int32)
        for r in range(b):
            if done[r]:
                new_tokens[r] = 0
                continue
            j = 0
            while j < k and proposals[r, j] == greedy[r, j]:
                j += 1
            # committed this round: proposals[:j] plus target's token at
            # the first mismatch (or after all k accepted) — all of them
            # target-greedy by construction
            committed = list(proposals[r, :j]) + [greedy[r, j]]
            for c in committed:
                if emitted[r] >= max_new_tokens or done[r]:
                    break
                out[r, emitted[r]] = c
                emitted[r] += 1
                if eos_id is not None and c == eos_id:
                    done[r] = True
            # pending token fed next round = last committed token;
            # its KEY is not yet in either cache (position n + j + ...)
            n[r] += 1 + j  # pending + accepted proposals are now cached
            new_tokens[r] = committed[-1] if committed else 0
        pending = new_tokens
        # no explicit rewind dispatch here: the NEXT round's spec_round
        # opens by setting both caches' write indices to the committed
        # length (done rows parked at 0) — rejected proposals' keys
        # become invisible the moment the index rewinds (slot-causal
        # masks), so the correction rides the next dispatch for free
        if eos_id is not None:
            done |= emitted >= max_new_tokens
        else:
            done = emitted >= max_new_tokens

    if eos_id is not None:
        # frozen rows keep emitting eos (generate()'s static-shape rule)
        for r in range(b):
            if emitted[r] < max_new_tokens and done[r]:
                out[r, emitted[r]:] = eos_id
    return jnp.asarray(out)
