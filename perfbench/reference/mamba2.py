"""Plain float32 reference of a Mamba-2 language model (arXiv:2405.21060)
trained by the over-the-air 1-bit round, and the benchmark's weight and
token generators for it.

The model follows the paper's block: RMS pre-norm, one in-projection to
(z, x, B, C, dt), a causal depthwise convolution with SiLU over (x, B, C),
dt = softplus(dt + dt_bias), the state-space dual (SSD) mixer, a skip
D * x, a gated RMS norm y * silu(z) and the out-projection; a tied
embedding and head. The mixer is computed in its quadratic (attention-like)
form over the whole sequence, y_t = sum_{s<=t} (C_t . B_s)
exp(sum_{s<r<=t} dt_r A) dt_s x_s, not in the chunked scan the program
runs. The program's norms scale by (1 + w) with w initialised at 0; the
reference uses the same convention.

Nothing here imports the program. Weights are made from the seed by
``init_params`` for both sides; the round follows ``reference.codec``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import codec


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_layers: int
    vocab: int
    d_state: int
    head_dim: int
    expand: int
    n_groups: int
    conv_width: int
    norm_eps: float

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        return cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)})

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def in_proj_dim(self) -> int:
        return self.conv_dim + self.d_inner + self.n_heads


def init_params(key, dims: Dims):
    """Seeded float32 weights in the layout the system's mamba2 model
    takes: {"embedding", "final_norm", "layers": {"ssm": {...},
    "ssm_norm"}}, layers stacked on a leading axis."""
    d, L, H = dims.d_model, dims.n_layers, dims.n_heads
    ks = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(ks[3], (L, H), minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    ssm = {
        "A_log": jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, H)),
                                  (L, H)),
        "D": jnp.ones((L, H)),
        "conv_b": jnp.zeros((L, dims.conv_dim)),
        "conv_w": jax.random.normal(ks[1], (L, dims.conv_width,
                                            dims.conv_dim))
        / math.sqrt(dims.conv_width),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
        "gate_norm": jnp.zeros((L, dims.d_inner)),
        "in_proj": jax.random.normal(ks[0], (L, d, dims.in_proj_dim))
        * math.sqrt(2.0 / d),
        "out_proj": jax.random.normal(ks[2], (L, dims.d_inner, d))
        * math.sqrt(2.0 / dims.d_inner),
    }
    return {"embedding": jax.random.normal(ks[4], (dims.vocab, d))
            / math.sqrt(d),
            "final_norm": jnp.zeros((d,)),
            "layers": {"ssm": ssm, "ssm_norm": jnp.zeros((L, d))}}


def make_tokens(key, t, n_workers: int, batch: int, seq: int, vocab: int):
    """Round t's token rows for every worker, (U, batch, seq + 1), uniform
    over the vocabulary: inputs are [..., :-1], targets [..., 1:]."""
    return jax.random.randint(jax.random.fold_in(key, t),
                              (n_workers, batch, seq + 1), 0, vocab,
                              jnp.int32)


# -- precision policies --------------------------------------------------------


def identity(x):
    return x


def _qdq(x, dtype, fmax):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, fmax / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8(x):
    """Operands of a matmul rounded to float8 (e4m3) with a per-tensor
    scale; their cotangents to e5m2 (the usual fp8 training recipe)."""
    return _qdq(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, ct):
    return (_qdq(ct, jnp.float8_e5m2, 57344.0),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)

POLICIES = {"float32": identity, "fp8": fp8}


# -- model ---------------------------------------------------------------------


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def ssd(x, dt, A, Bm, Cm, q):
    """Quadratic SSD. x (b, s, H, P), dt (b, s, H), A (H,), Bm/Cm
    (b, s, G, N); heads h use group h // (H / G)."""
    b, s, H, P = x.shape
    G = Bm.shape[2]
    R = H // G
    xg = x.reshape(b, s, G, R, P).transpose(2, 0, 1, 3, 4)
    dtg = dt.reshape(b, s, G, R).transpose(2, 0, 1, 3)
    Ag = A.reshape(G, R)
    Bg = Bm.transpose(2, 0, 1, 3)
    Cg = Cm.transpose(2, 0, 1, 3)
    later = jnp.arange(s)[:, None] > jnp.arange(s)[None, :]      # t > s
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]    # t >= s

    def one(args):
        xr, dtr, a, bb, cc = args
        da = (dtr * a).transpose(0, 2, 1)                 # (b, R, s)
        # seg[t, s] = sum of da over s < r <= t, by a cumulative sum down t
        seg = jnp.cumsum(jnp.where(later, da[..., :, None], 0.0), axis=-2)
        decay = jnp.where(causal, jnp.exp(seg), 0.0)      # (b, R, t, s)
        cb = jnp.einsum("btn,bsn->bts", q(cc), q(bb))
        w = cb[:, None] * decay * dtr.transpose(0, 2, 1)[:, :, None, :]
        return jnp.einsum("brts,bsrp->btrp", w, q(xr))

    y = jax.lax.map(jax.checkpoint(one), (xg, dtg, Ag, Bg, Cg))
    return y.transpose(1, 2, 0, 3, 4).reshape(b, s, H, P)


def mixer(h, p, dims: Dims, q):
    di, H, P = dims.d_inner, dims.n_heads, dims.head_dim
    GN = dims.n_groups * dims.d_state
    b, s, _ = h.shape
    zx = q(h) @ q(p["in_proj"])
    z = zx[..., :di]
    xbc = zx[..., di:di + dims.conv_dim]
    dtr = zx[..., di + dims.conv_dim:]
    W = dims.conv_width
    xp = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + s] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(b, s, H, P)
    Bm = xbc[..., di:di + GN].reshape(b, s, dims.n_groups, dims.d_state)
    Cm = xbc[..., di + GN:].reshape(b, s, dims.n_groups, dims.d_state)
    dt = jax.nn.softplus(dtr + p["dt_bias"])
    y = ssd(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm, q)
    y = (y + xs * p["D"][:, None]).reshape(b, s, di)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_norm"], dims.norm_eps)
    return q(y) @ q(p["out_proj"])


def lm_loss(params, tokens, targets, dims: Dims, q=identity, keep=None):
    """Mean next-token cross-entropy of one worker's (batch, seq) rows;
    ``keep`` (seq,) bool, when given, limits the mean to those positions."""
    x = params["embedding"][tokens] * math.sqrt(dims.d_model)

    def layer(x, lp):
        return x + mixer(rmsnorm(x, lp["ssm_norm"], dims.norm_eps),
                         lp["ssm"], dims, q), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = rmsnorm(x, params["final_norm"], dims.norm_eps)
    logits = q(x) @ q(params["embedding"]).T
    nll = jax.nn.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    if keep is None:
        return jnp.mean(nll)
    w = jnp.broadcast_to(keep, nll.shape).astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.sum(w)


# -- the flat chunked layout of the round ------------------------------------


class Layout:
    """Leaves raveled in pytree order into one vector, zero-padded to a
    whole number of D_c chunks, the count rounded up to ``pad_to``."""

    def __init__(self, tree, chunk: int, pad_to: int):
        leaves, self.treedef = jax.tree_util.tree_flatten_with_path(tree)
        self.names = [jax.tree_util.keystr(p) for p, _ in leaves]
        self.shapes = [tuple(x.shape) for _, x in leaves]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.offsets = [int(o) for o in np.cumsum([0] + self.sizes[:-1])]
        self.D = sum(self.sizes)
        n = -(-self.D // chunk)
        self.n_chunks = -(-n // pad_to) * pad_to
        self.chunk = chunk

    def to_master(self, tree):
        flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                                for x in jax.tree_util.tree_leaves(tree)])
        return jnp.pad(flat, (0, self.n_chunks * self.chunk - self.D)
                       ).reshape(self.n_chunks, self.chunk)

    def to_tree(self, master):
        flat = master.reshape(-1)
        return jax.tree_util.tree_unflatten(self.treedef, [
            flat[o:o + n].reshape(s)
            for o, n, s in zip(self.offsets, self.sizes, self.shapes)])

    def leaf_norms(self, master):
        """L2 norm of each leaf's entries, (n_leaves,)."""
        flat = master.reshape(-1)
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(flat[o:o + n])))
                          for o, n in zip(self.offsets, self.sizes)])


# -- the round -----------------------------------------------------------------


class Hyper(NamedTuple):
    chunk: int
    measure: int
    topk: int
    decode_topk: int
    biht_iters: int
    tau: float
    phi_seed: int
    noise_var: float
    p_max: float
    lr: float


class Carry(NamedTuple):
    master: jnp.ndarray      # (n_chunks, D_c)
    m: jnp.ndarray
    v: jnp.ndarray
    residual: jnp.ndarray    # (U, n_chunks, D_c)


def init_carry(master, n_workers: int) -> Carry:
    return Carry(master, jnp.zeros_like(master), jnp.zeros_like(master),
                 jnp.zeros((n_workers,) + master.shape))


def _blocks(x, rows: int):
    return x.reshape((x.shape[0] // rows, rows) + x.shape[1:])


def grads(master, tokens, targets, layout: Layout, dims: Dims, q=identity,
          keep=None):
    """Per-worker losses (U,) and gradients (U, n_chunks, D_c)."""
    params = layout.to_tree(master)
    out = [jax.value_and_grad(lm_loss)(params, tokens[u], targets[u], dims,
                                       q, keep)
           for u in range(tokens.shape[0])]
    return (jnp.stack([l for l, _ in out]),
            jnp.stack([layout.to_master(g) for _, g in out]))


def codec_step(carry: Carry, g, t, key, hp: Hyper, block: int):
    """EF top-kappa, uplink, MAC, decode and Adam for round t (0-based).
    Returns (carry', ghat)."""
    phi = codec.make_phi(hp.phi_seed, hp.measure, hp.chunk)
    U = g.shape[0]

    def up(args):
        gb, rb = args
        corrected = gb + rb
        sp = codec.topk(corrected, hp.topk)
        s, mag = codec.uplink(sp, phi)
        return s, mag, corrected - sp

    signs, mags, res = [], [], []
    for u in range(U):
        s, mag, r = jax.lax.map(up, (_blocks(g[u], block),
                                     _blocks(carry.residual[u], block)))
        signs.append(s.reshape(-1, hp.measure))
        mags.append(mag.reshape(-1))
        res.append(r.reshape(g.shape[1:]))
    k_t = jax.random.fold_in(key, t)
    h = codec.fades(jax.random.fold_in(k_t, 0), U)
    b_t = jnp.min(h * jnp.sqrt(jnp.float32(hp.p_max)))   # all scheduled
    field = codec.noise(jax.random.fold_in(k_t, 1),
                        (g.shape[1], hp.measure), hp.noise_var)
    y, mbar = codec.aggregate(jnp.stack(signs), jnp.stack(mags),
                              jnp.ones((U,)), b_t, field)

    def down(args):
        yb, mb = args
        return codec.decode(yb, mb, phi, hp.decode_topk, hp.biht_iters,
                            hp.tau)

    ghat = jax.lax.map(down, (_blocks(y, block), _blocks(mbar, block))
                       ).reshape(g.shape[1:])
    master, m, v = codec.adam(carry.master, carry.m, carry.v, t + 1, ghat,
                              hp.lr)
    return Carry(master, m, v, jnp.stack(res)), ghat
