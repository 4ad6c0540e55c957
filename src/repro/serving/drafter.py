"""Draft proposers for speculative decoding.

Draft-and-verify decoding needs a cheap source of K candidate tokens per
row per step; the engine then scores all K+1 positions in one
``prefill_chunk`` call (the chunked-prefill machinery *is* the verifier)
and keeps the leading run that matches the verifier's own argmax.  A
drafter therefore never affects *correctness* — a bad draft only lowers
the accept rate — which is what lets both implementations here cut
corners aggressively.

Two drafters, one contract (``repro.serving.config.SpecConfig`` picks):

  * :class:`PromptLookupDrafter` (``drafter="prompt_lookup"``) — n-gram
    prompt lookup: match the last ``ngram`` tokens ending at the row's
    current position against the row's own earlier tokens and propose
    the continuation after the most recent match.  Stateless (pure
    function of the token buffer), family-agnostic, and essentially
    free — the classic win on repetitive suffixes (code, quotations,
    summarization).
  * :class:`HybridSSMDrafter` (``drafter="hybrid_ssm"``) — the ssm half
    of a hybrid drafting for the attention layers: the hybrid family's
    own Mamba blocks (shared weights — ``params["groups"]`` reshaped to
    the stacked-layer form) run as a K-step draft model, skipping the
    shared attention/MLP block that makes full steps expensive.  The
    drafter carries *private* recurrent state (``drf_ssm``/``drf_conv``/
    ``drf_pos`` keys in the decode-state dict — the hidden trajectory
    without attention differs from the model's own, so the model's
    ``ssm`` state cannot be borrowed), advanced only on *committed*
    tokens: proposal steps run on a discarded copy, because an SSM
    cannot roll back a rejected suffix.

Both run entirely inside the engine's jitted ``_spec_n`` (fixed shapes,
no host syncs — R002 scopes this file); the ``stateful`` flag tells the
engine whether to allocate drafter state and ingest committed tokens
(``ingest`` keeps the invariant ``drf_pos <= progress``, catching up
lazily with a statically-bounded chunk).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import components as C


class PromptLookupDrafter:
    """N-gram prompt lookup: propose the continuation after the most
    recent earlier occurrence of the last ``ngram`` tokens."""

    stateful = False

    def __init__(self, spec) -> None:
        self.k = int(spec.k)
        self.ngram = int(spec.ngram)

    def init_state(self, batch: int):
        return {}

    def ingest(self, params, state, tokens, upto, chunk: int):
        return state

    def propose(self, params, state, tokens, progress, active):
        """(B, K) drafts for every row; pure gathers over the token
        buffer (positions ``<= progress`` are real — prompt then
        committed tokens; anything drafted from beyond the frontier is
        garbage the verifier simply rejects)."""
        b, max_len = tokens.shape
        n, k = self.ngram, self.k
        prog = jnp.clip(progress, 0, max_len - 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (b, max_len), 1)
        # key: the n tokens ending at the current position
        koff = jnp.arange(n, dtype=jnp.int32) - (n - 1)
        kidx = jnp.clip(prog[:, None] + koff[None, :], 0, max_len - 1)
        key = jnp.take_along_axis(tokens, kidx, axis=1)        # (B, n)
        # window equality: does the n-gram ending at column i match?
        eq = jnp.ones((b, max_len), bool)
        for j in range(n):
            widx = jnp.clip(col + (j - (n - 1)), 0, max_len - 1)
            eq &= (
                jnp.take_along_axis(tokens, widx, axis=1)
                == key[:, j][:, None]
            )
        # candidate windows must lie fully inside the committed prefix
        match = eq & (col >= n - 1) & (col < progress[:, None])
        i_best = jnp.max(jnp.where(match, col, -1), axis=1)    # (B,)
        found = i_best >= 0
        # the committed suffix ``i_best+1 .. prog`` is the continuation of
        # the most recent match; reading it modulo its length keeps short
        # cycles (period < K, e.g. a converged constant) proposing the
        # cycle instead of running past the frontier into garbage
        period = jnp.maximum(prog - i_best, 1)
        offs = jnp.arange(k, dtype=jnp.int32)[None, :]
        didx = jnp.clip(
            i_best[:, None] + 1 + offs % period[:, None],
            0, max_len - 1,
        )
        drafts = jnp.take_along_axis(tokens, didx, axis=1)     # (B, K)
        # no match: repeat the current token (worst case: accept rate 0)
        cur = jnp.take_along_axis(tokens, prog[:, None], axis=1)
        drafts = jnp.where(found[:, None], drafts, cur)
        return drafts.astype(jnp.int32), state


class HybridSSMDrafter:
    """The hybrid family's Mamba layers as a weight-shared draft model.

    ``params["groups"]`` leaves are ``(g, attn_every, ...)``; reshaping
    the leading two axes gives the ssm-family stacked-layer form, so the
    draft model is one ``lax.scan`` of ``mamba_decode_block`` over all
    ``n_layers`` Mamba blocks plus the shared final norm and head —
    attention (and its KV traffic) is exactly what gets skipped.
    """

    stateful = True

    def __init__(self, spec, cfg) -> None:
        if cfg.family != "hybrid":
            raise ValueError(
                "drafter='hybrid_ssm' drafts with the hybrid family's "
                f"Mamba layers — family 'hybrid' required, got "
                f"{cfg.family!r}"
            )
        self.k = int(spec.k)
        self.cfg = cfg

    def init_state(self, batch: int):
        """Private drafter recurrence (``lm.reset_decode_rows`` zeroes
        these with the row's other caches; spill/restore leaves them in
        the lane like the live ``ssm``/``conv`` state)."""
        cfg = self.cfg
        return {
            "drf_ssm": jnp.zeros(
                (cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                 cfg.ssm_state), jnp.float32,
            ),
            "drf_conv": jnp.zeros(
                (cfg.n_layers, batch, cfg.ssm_conv - 1, cfg.conv_dim),
                cfg.dtype_(),
            ),
            "drf_pos": jnp.zeros((batch,), jnp.int32),
        }

    def _layers(self, params):
        # (g, attn_every, ...) group leaves -> (n_layers, ...) stacked
        return jax.tree_util.tree_map(
            lambda leaf: leaf.reshape(
                leaf.shape[0] * leaf.shape[1], *leaf.shape[2:]
            ),
            params["groups"],
        )

    def ingest(self, params, state, tokens, upto, chunk: int):
        """Advance the drafter recurrence over committed tokens
        ``drf_pos .. upto-1`` (one masked SSD prefill of static width
        ``chunk`` — rows already caught up, or frozen/spilled rows whose
        ``upto`` has not moved, get width 0 and are no-ops)."""
        cfg = self.cfg
        b, max_len = tokens.shape
        dpos = state["drf_pos"]
        w = jnp.clip(upto - dpos, 0, chunk)
        offs = jnp.arange(chunk, dtype=jnp.int32)
        gidx = jnp.clip(dpos[:, None] + offs[None, :], 0, max_len - 1)
        toks = jnp.take_along_axis(tokens, gidx, axis=1)
        x = params["embed"][toks].astype(cfg.dtype_())
        valid = offs[None, :] < w[:, None]

        def body(x, inp):
            p, s_ssm, s_conv = inp
            x, s_ssm, s_conv = C.mamba_prefill_block(
                cfg, p["mamba"], x, s_ssm, s_conv, valid
            )
            return x, (s_ssm, s_conv)

        _, (ssm, conv) = jax.lax.scan(
            body, x,
            (self._layers(params), state["drf_ssm"], state["drf_conv"]),
        )
        return {**state, "drf_ssm": ssm, "drf_conv": conv,
                "drf_pos": dpos + w}

    def propose(self, params, state, tokens, progress, active):
        """Catch the recurrence up to ``progress`` (committing that
        advance), then run K greedy draft steps on a *discarded* copy —
        rejected proposals must leave no trace in a state that cannot
        roll back."""
        state = self.ingest(params, state, tokens, progress, self.k + 1)
        cfg = self.cfg
        b, max_len = tokens.shape
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        layers = self._layers(params)
        tok0 = jnp.take_along_axis(
            tokens, jnp.clip(progress, 0, max_len - 1)[:, None], axis=1
        )[:, 0]

        def step(carry, _):
            ssm, conv, tok = carry
            x = params["embed"][tok].astype(cfg.dtype_())

            def body(x, inp):
                p, s_ssm, s_conv = inp
                x, s_ssm, s_conv = C.mamba_decode_block(
                    cfg, p["mamba"], x, s_ssm, s_conv
                )
                return x, (s_ssm, s_conv)

            x, (ssm, conv) = jax.lax.scan(body, x, (layers, ssm, conv))
            h = C.norm(cfg, params["ln_f"], x)
            nxt = jnp.argmax(C.dense(h, head), axis=-1).astype(jnp.int32)
            return (ssm, conv, nxt), nxt

        _, drafts = jax.lax.scan(
            step, (state["drf_ssm"], state["drf_conv"], tok0),
            None, length=self.k,
        )
        return drafts.T, state                                  # (B, K)


def make_drafter(spec, cfg):
    """Drafter factory for ``SpecConfig.drafter`` (family-validated)."""
    if spec.drafter == "prompt_lookup":
        return PromptLookupDrafter(spec)
    if spec.drafter == "hybrid_ssm":
        return HybridSSMDrafter(spec, cfg)
    raise ValueError(
        f"unknown drafter {spec.drafter!r} "
        "(expected 'prompt_lookup' or 'hybrid_ssm')"
    )
