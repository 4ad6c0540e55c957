"""The ssm family: Mamba-2 (SSD, arXiv:2405.21060) blocks with no MLP and
no attention, as the benchmark sees it.

Sizes (:class:`Arch`, read from the published ``config.json`` keys and
the ``ssm_cfg`` values the configuration file writes out), the program
attributes they must match, the weight table, the plain reference forward
and the work the traffic asks of each layer.  It imports nothing of the
program under test.

Weights, drawn with ``weights.py``'s two kinds so that no term is a no-op
the check could not see: projections N(0, 1/fan_in); the embedding N(0,
0.02); the conv weight N(0, 1/d_conv) and its bias N(0, 0.5**2); ``D``
and every norm weight 1 + N(0, s**2) (s 0.5 and 0.1); ``dt_bias`` N(0, 1).
``a_log`` is N(0, 2**2): A = -exp(a_log) then spans e**-6 to e**6 over a
layer's heads, so while most heads forget within a few positions, some
decay over hundreds, and a state carried wrongly from one prefill chunk
to the next changes what they read out.

Reference, per layer, from the published equations with the residual
stream in float32: RMSNorm; in-projection into z | xBC | dt; causal
depthwise conv over xBC (d_conv taps) with its bias, SiLU, split into x,
B and C; dt = softplus(dt + dt_bias), A = -exp(a_log); the recurrence
h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t, y_t = C_t . h_t + D x_t as a
scan over positions (not the chunked dual form the kernel computes);
y * silu(z), RMSNorm with weight (the gated norm, norm before gate
false); out-projection; residual.  Then the final RMSNorm and the tied
head.  Every norm uses the configuration's eps.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import work as W
from reference import F32, _prep, _rms
from weights import dtype_of


@dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    vocab: int             # rows of the embedding, padded as published
    d_state: int
    d_conv: int
    expand: int
    head_dim: int
    eps: float
    residual_f32: bool
    tied: bool
    dtype: str             # weights and activations as served

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:         # x, B and C of one group
        return self.d_inner + 2 * self.d_state


def arch_of(cfg: dict) -> Arch:
    """The :class:`Arch` of a configuration file: ``config.json``'s keys
    beside the ``ssm_cfg`` values written out."""
    ssm = cfg["ssm_cfg"]
    if ssm["layer"] != "Mamba2" or ssm["ngroups"] != 1 or cfg["d_intermediate"] \
            or cfg["attn_layer_idx"] or not cfg["rms_norm"]:
        raise ValueError("the ssm family reads Mamba-2 blocks of one group, "
                         "RMSNorm, no MLP and no attention layers")
    return Arch(
        n_layers=cfg["n_layer"], d_model=cfg["d_model"],
        vocab=cfg["vocab_size"], d_state=ssm["d_state"],
        d_conv=ssm["d_conv"], expand=ssm["expand"], head_dim=ssm["headdim"],
        eps=cfg["norm_epsilon"], residual_f32=bool(cfg["residual_in_fp32"]),
        tied=bool(cfg["tie_embeddings"]), dtype=cfg["torch_dtype"])


def program_sizes(a: Arch) -> dict:
    """``{ArchConfig attribute: value}`` the program's model must have."""
    return {"family": "ssm", "n_layers": a.n_layers, "d_model": a.d_model,
            "vocab_size": a.vocab, "dtype": a.dtype, "d_ff": 0,
            "ssm_state": a.d_state, "ssm_head_dim": a.head_dim,
            "ssm_expand": a.expand, "ssm_conv": a.d_conv,
            "d_inner": a.d_inner, "ssm_heads": a.n_heads,
            "tie_embeddings": a.tied, "norm_eps": a.eps,
            "residual_f32": a.residual_f32}


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def shapes(arch: Arch) -> dict:
    """``{path: (shape, dtype, kind, scale)}`` of every leaf."""
    if not arch.tied:
        raise ValueError("the ssm family's head is the tied embedding")
    d, L, dt = arch.d_model, arch.n_layers, dtype_of(arch)
    di, n, h, cd = arch.d_inner, arch.d_state, arch.n_heads, arch.conv_dim
    m = "layers/mamba/"
    return {
        "embed": ((arch.vocab, d), dt, "normal", 0.02),
        "ln_f": ((d,), dt, "norm", 0.1),
        m + "w_in": ((L, d, 2 * di + 2 * n + h), dt, "normal", d ** -0.5),
        m + "conv_w": ((L, arch.d_conv, cd), dt, "normal", arch.d_conv ** -0.5),
        m + "conv_b": ((L, cd), dt, "normal", 0.5),
        m + "a_log": ((L, h), F32, "normal", 2.0),
        m + "dt_bias": ((L, h), F32, "normal", 1.0),
        m + "d_skip": ((L, h), F32, "norm", 0.5),
        m + "w_out": ((L, di, d), dt, "normal", di ** -0.5),
        m + "ln": ((L, d), dt, "norm", 0.1),
        m + "ln_inner": ((L, di), dt, "norm", 0.1),
    }


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def _recurrence(a, xs, b, c, dt):
    """h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t; y_t = C_t . h_t, one
    position at a time from h = 0.  xs (S, H, P), b and c (S, N), dt
    (S, H), a (H,) -> y (S, H, P)."""
    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return h, jnp.sum(h * c_t[None, None, :], axis=-1)

    h0 = jnp.zeros(xs.shape[1:] + b.shape[1:], F32)
    return jax.lax.scan(step, h0, (xs, b, c, dt))[1]


def _mamba_layer(arch: Arch, quantize, x, lp):
    s = x.shape[0]
    di, n, h, k = arch.d_inner, arch.d_state, arch.n_heads, arch.d_conv
    p = {key: v.astype(F32) for key, v in lp["mamba"].items()}
    w_in = _prep(lp["mamba"]["w_in"], quantize)
    w_out = _prep(lp["mamba"]["w_out"], quantize)
    zxbcdt = _rms(x, p["ln"], arch.eps) @ w_in
    z, xbc = zxbcdt[:, :di], zxbcdt[:, di: di + arch.conv_dim]
    dt = jax.nn.softplus(zxbcdt[:, di + arch.conv_dim:] + p["dt_bias"])
    pad = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        pad[i: i + s] * p["conv_w"][i] for i in range(k)))
    xs = xbc[:, :di].reshape(s, h, arch.head_dim)
    y = _recurrence(-jnp.exp(p["a_log"]), xs, xbc[:, di: di + n],
                    xbc[:, di + n:], dt)
    y = (y + p["d_skip"][:, None] * xs).reshape(s, di) * jax.nn.silu(z)
    return x + _rms(y, p["ln_inner"], arch.eps) @ w_out


@functools.partial(jax.jit, static_argnums=(0, 4))
def logits(arch: Arch, params, tokens, positions, quantize: str = ""):
    """(K, V) float32 logits of the plain forward of ``tokens`` (S,) at
    ``positions`` (K,).  Tokens past the sequence's end may be anything:
    the forward is causal."""
    with jax.default_matmul_precision("highest"):
        emb = _prep(params["embed"], quantize, axis=1)    # (V, d)
        x, _ = jax.lax.scan(
            lambda x, lp: (_mamba_layer(arch, quantize, x, lp), None),
            emb[tokens], params["layers"])
        hid = _rms(x[positions], params["ln_f"].astype(F32), arch.eps)
        return hid @ emb.T


# --------------------------------------------------------------------------
# work
# --------------------------------------------------------------------------

def matmul_params(arch: Arch) -> int:
    """Weights that every token multiplies, the logits' head excluded:
    the in- and out-projections."""
    d, di = arch.d_model, arch.d_inner
    return arch.n_layers * (d * (2 * di + 2 * arch.d_state + arch.n_heads)
                            + di * d)


def head_params(arch: Arch) -> int:
    return arch.vocab * arch.d_model


def state_bytes(arch: Arch) -> int:
    """The float32 SSM state of one row, all layers."""
    return (arch.n_layers * arch.n_heads * arch.head_dim * arch.d_state
            * 4)


def ssd_flops_per_token(arch: Arch) -> float:
    """State update and read-out (4 H P N) and the D skip (2 H P), all
    layers."""
    hp = arch.n_heads * arch.head_dim
    return float(arch.n_layers * (4 * hp * arch.d_state + 2 * hp))


def ssd(arch: Arch, tokens: int, *, chunk: int) -> W.Work:
    """SSD over ``tokens`` positions of one request, fed in pieces of
    ``chunk`` positions (1 for decode): the float32 state read and written
    once per piece, and each token's x, B, C, y (served dtype) and dt
    (float32) moved once."""
    if tokens <= 0:
        return W.Work()
    item = W.ITEM[arch.dtype]
    hp, n = arch.n_heads * arch.head_dim, arch.d_state
    per_token = arch.n_layers * ((2 * hp + 2 * n) * item + 4 * arch.n_heads)
    pieces = -(-tokens // chunk)
    return W.Work(tokens * ssd_flops_per_token(arch),
                  pieces * 2 * state_bytes(arch) + tokens * per_token)


def gemm(arch: Arch, tokens: int, head_tokens: int, dispatches: int) -> W.Work:
    """Projections: 2 * tokens * weights operations.  Bytes: the weights
    read once per dispatch, and every token's input and output rows."""
    item = W.ITEM[arch.dtype]
    flops = 2.0 * (tokens * matmul_params(arch) + head_tokens * head_params(arch))
    w_bytes = (matmul_params(arch) + head_params(arch)) * item * dispatches
    d, di = arch.d_model, arch.d_inner
    rows = (d + 2 * di + 2 * arch.d_state + arch.n_heads) + (di + d)
    act = (tokens * rows * arch.n_layers + head_tokens * (d + arch.vocab)) \
        * item
    return W.Work(flops, w_bytes + act)


def window_work(arch: Arch, recs, chunk: int, dispatches: int) -> dict:
    """Work of the requests ``recs`` (each with ``plen`` and ``max_new``)
    by layer: SSD per phase, the projections, and the model's operations
    (projections, the head on the positions whose logits are used, SSD;
    the conv and the norms, under 2% of them, left out).  Each prompt is
    fed in pieces of ``chunk`` positions, then each generated token but
    the last one at a time; the weights are read once per dispatch of a
    step program."""
    ssd_pre, ssd_dec = W.Work(), W.Work()
    tokens = head = 0
    for r in recs:
        fed = r.plen + r.max_new - 1          # positions fed to the model
        ssd_pre += ssd(arch, r.plen, chunk=chunk)
        ssd_dec += ssd(arch, fed - r.plen, chunk=1)
        tokens += fed
        head += r.max_new                     # positions whose logits are used
    return {
        "ssd": (ssd_pre, ssd_dec),
        "gemm": gemm(arch, tokens, head, dispatches),
        "model_flops": 2.0 * (tokens * matmul_params(arch)
                              + head * head_params(arch))
        + tokens * ssd_flops_per_token(arch),
    }
