"""Chunked prefill: the second decode executable (ISSUE 12 tentpole a).

PR 8's `DecodeEngine` prefills prompts through the per-token step
executable — one prompt token per engine boundary, so a 2k-token
prompt pays 2k boundaries of host bookkeeping (and 2k dispatches)
before emitting anything. `ChunkedPrefill` compiles ONE more
executable with shape ``[max_slots, chunk]`` that retires prompt
tokens in fixed-size blocks: time-to-first-token drops from
O(prompt_len) boundaries to O(prompt_len / chunk), while in-flight
decodes keep streaming through the unchanged per-token executable at
every boundary (the engine runs the prefill dispatch first, then the
token step — prefilling and decoding slots interleave, Dragon-Alpha's
lean-kernel-set discipline: one block executable, not a kernel per
feature).

Bit-identity is the correctness bar, and it is held BY CONSTRUCTION:
the block executable's body is a ``lax.fori_loop`` over the SAME
masked single-token function the step executable runs (`masked_fn` on
the decode models), at the same ``[max_slots]`` shapes — position j of
a chunk computes exactly what the per-token path would have computed
at that boundary, so the engine's output for a chunked prompt equals
the offline single-request decode loop token for token (asserted for
a >=512-token prompt in tests).

Masking: ``counts[s]`` is how many of slot s's block tokens are real.
Iterations past a slot's count route their KV-pool writes to scratch
page 0 and keep RNN carries via ``jnp.where`` — an idle or decoding
slot passes through a prefill dispatch bit-unchanged, the same
invariant the token step already holds for idle slots.

The same class doubles as the SPECULATIVE VERIFIER (tentpole c): a
``[max_slots, k+1]`` block of draft tokens through `run()` returns the
target's next-token argmax at every position in one batched call —
the per-shape jit cache means chunk-prefill and verify are two
executables of one traced function (or ONE executable when
``chunk == k + 1``).
"""

from __future__ import annotations

import numpy as np

from deeplearning4j_tpu.telemetry import compile_ledger


class ChunkedPrefill:
    """``[max_slots, width]`` block executable over a decode model's
    masked token step. One instance serves every block width (the jit
    cache keys on the block shape); the engine warms the widths it
    will use so steady state never compiles."""

    def __init__(self, model, chunk):
        import jax

        if int(chunk) < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.model = model
        self.chunk = int(chunk)
        # the state is the loop's carry: donated where the model's own
        # step donates it, so the block too writes the pool in place
        self._donation = tuple(getattr(model, "state_donation", ()))
        self._jit = jax.jit(self._fn, donate_argnums=self._donation)
        # ISSUE 20: the prefill/verify executable rides the persistent
        # store like the token step (unsharded lane only; identity
        # when the store is off or the model has no program digest)
        # (the block width rides the per-signature key, not the
        # program: prefill and verify instances share store entries)
        if getattr(model, "_store_program", None) is not None:
            from deeplearning4j_tpu.serving.decode import _maybe_store

            self._jit = _maybe_store(self._jit, "decode:prefill",
                                     model, "prefill",
                                     donation=self._donation)

    def _fn(self, params, state, blocks, pos0, counts, table):
        import jax.numpy as jnp
        from jax import lax

        S, V = blocks.shape

        def body(j, carry):
            state, outs = carry
            active = j < counts
            pos = jnp.where(active, pos0 + j, 0)
            nxt, state = self.model.masked_fn(
                params, state, blocks[:, j], pos, table, active)
            outs = outs.at[:, j].set(jnp.where(active, nxt, -1))
            return state, outs

        outs0 = jnp.full((S, V), -1, jnp.int32)
        state, outs = lax.fori_loop(0, V, body, (state, outs0))
        return outs, state

    def launch(self, state, blocks, pos0, counts, table, site=None):
        """Dispatch the block and return without waiting: ``outs`` is
        still on the device (the engine times the wait for it as its
        own phase). The ``state`` passed is consumed where the model
        donates it: go on with the one returned."""
        args = (self.model.params_for_step(), state,
                np.ascontiguousarray(blocks, dtype=np.int32),
                np.ascontiguousarray(pos0, dtype=np.int32),
                np.ascontiguousarray(counts, dtype=np.int32), table)
        outs, state = self._jit(*args)
        if site is not None:
            compile_ledger.note_step(site, self._jit, args,
                                     donation=self._donation)
        return outs, state

    def run(self, state, blocks, pos0, counts, table, site=None):
        """Consume ``counts[s]`` tokens of ``blocks[s]`` per slot
        starting at ``pos0[s]``. Returns ``(outs, state)`` where
        ``outs[s, j]`` is the model's next-token argmax after consuming
        block token j (-1 past a slot's count) — ignored by prefill,
        consumed by speculative verify."""
        outs, state = self.launch(state, blocks, pos0, counts, table,
                                  site=site)
        return np.asarray(outs), state

    def warmup(self, state, table, widths=None, site=None):
        """Compile every block width the engine will dispatch (all
        counts zero: the state rides through untouched except
        scratch). Returns the state to go on with."""
        S = self.model.max_slots
        z = np.zeros((S,), np.int32)
        for width in (widths or (self.chunk,)):
            _, state = self.run(state, np.zeros((S, int(width)), np.int32),
                                z, z, table, site=site)
        return state
