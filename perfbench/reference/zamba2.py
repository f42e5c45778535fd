"""Plain float32 reference of a Zamba2 language model (arXiv:2411.15242;
``transformers``' ``models/zamba2/modeling_zamba2.py``) trained by the
over-the-air 1-bit round, and the benchmark's weight generator for it.

Each layer is a Mamba-2 layer: RMS pre-norm, one in-projection to
(z, x, B, C, dt), a causal depthwise convolution with SiLU over (x, B, C),
dt = softplus(dt + dt_bias), the state-space dual (SSD) mixer, a skip
D * x, the gated RMS norm of y * silu(z) over each of the ``n_groups``
groups of d_inner / n_groups, and the out-projection. Before each layer in
``hybrid_layer_ids``, call c runs shared block c % num_mem_blocks on
[h, embedding] (2 d wide): RMS norm, multi-head attention (q, k, v from
2 d, RoPE over the whole head, scale (head_dim / 2)^-0.5, one causal
softmax, ``o_proj`` to d), RMS norm, and the gated-GELU MLP act(gate) * up
whose ``gate_up`` adds call c's LoRA adapter; then call c's d x d
``linear``. Its output t joins the layer's input at the Mamba norm only:
h' = h + mamba(norm(h + t)). The embedding is not scaled; the head is the
embedding, tied.

The mixer is computed in its quadratic form, one head at a time over the
whole sequence, y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A)
dt_s x_s, not in the chunked scan the program runs; attention is one full
causal softmax per head, not the program's query blocks.

Departures from ``modeling_zamba2.py``, each also the program's:

- norms scale by (1 + w) with w initialised at 0 (there: w, at 1): the
  same function of the weights, w_there = 1 + w_here;
- dt is not clamped below (its torch path clamps at ``time_step_min``,
  its kernel path at ``time_step_limit``, (0, inf), i.e. not at all);
- weights are random, from the seed (``init_params``), and the gated
  norm multiplies by its weight in float32 before any cast.

Nothing here imports the program. ``Layout`` is the round's flat order
over ``mp`` model shards, as the round defines it (each leaf split along
its largest dim divisible by mp, a stacked collection's leading dim kept
whole, ties to the trailing dim; section m holds the m-th slice of every
leaf). The round follows ``reference.codec`` through ``codec_step``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench.reference import codec
# the round's carry, hyper-parameters, token generator and fp8 policy are
# mamba2's, re-exported for the driver
from perfbench.reference.mamba2 import (Carry, Hyper, fp8,  # noqa: F401
                                        identity, init_carry, make_tokens)

STACKED = ("layers", "shared_blocks", "hybrid")


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
    n_heads_attn: int
    attn_head_dim: int
    ffn: int
    adapter_rank: int
    num_mem_blocks: int
    hybrid_layer_ids: Tuple[int, ...]
    rope_theta: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """From a configuration file's keys (those of the model's
        ``config.json``)."""
        return cls(d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
                   vocab=c["vocab_size"], d_state=c["mamba_d_state"],
                   head_dim=c["mamba_headdim"], expand=c["mamba_expand"],
                   n_groups=c["mamba_ngroups"], conv_width=c["mamba_d_conv"],
                   norm_eps=c["rms_norm_eps"],
                   n_heads_attn=c["num_attention_heads"],
                   attn_head_dim=c["attention_head_dim"],
                   ffn=c["ffn_hidden_size"], adapter_rank=c["adapter_rank"],
                   num_mem_blocks=c["num_mem_blocks"],
                   hybrid_layer_ids=tuple(c["hybrid_layer_ids"]),
                   rope_theta=float(c["rope_theta"]))

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

    @property
    def n_calls(self) -> int:
        return len(self.hybrid_layer_ids)


def switched(low):
    """The matmul operand policy ``fp8`` where the traced bool ``low`` is
    set, else ``identity`` (exactly: the other branch is not taken), so
    that the float32 reference and its fp8 control run one compiled
    program."""
    return lambda x: jnp.where(low, fp8(x), x)


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape) / math.sqrt(fan_in)


def init_params(key, dims: Dims):
    """Seeded float32 weights in the layout the system's hybrid model
    takes: {"embedding", "final_norm", "hybrid": per call {"adapter_in",
    "adapter_out", "linear"}, "layers": {"ssm": {...}, "ssm_norm"},
    "shared_blocks": {"attn": {"wk", "wo", "wq", "wv"}, "attn_norm",
    "down", "ffn_norm", "gate_up"}}, each collection stacked on a leading
    axis."""
    d, L, H = dims.d_model, dims.n_layers, dims.n_heads
    d2, F, r = 2 * d, dims.ffn, dims.adapter_rank
    nb, nc = dims.num_mem_blocks, dims.n_calls
    Ha, hd = dims.n_heads_attn, dims.attn_head_dim
    ks = jax.random.split(key, 16)
    dt = jnp.exp(jax.random.uniform(ks[3], (L, H), minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    ssm = {
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1.0, H + 1.0)), (L, H)),
        "D": jnp.ones((L, H)),
        "conv_b": jnp.zeros((L, dims.conv_dim)),
        "conv_w": jax.random.normal(ks[1], (L, dims.conv_width,
                                            dims.conv_dim))
        / math.sqrt(dims.conv_width),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
        "gate_norm": jnp.zeros((L, dims.d_inner)),
        "in_proj": _normal(ks[0], (L, d, dims.in_proj_dim), d),
        "out_proj": _normal(ks[2], (L, dims.d_inner, d), dims.d_inner),
    }
    blocks = {
        "attn": {"wk": _normal(ks[5], (nb, d2, Ha, hd), d2),
                 "wo": _normal(ks[6], (nb, Ha, hd, d), Ha * hd),
                 "wq": _normal(ks[7], (nb, d2, Ha, hd), d2),
                 "wv": _normal(ks[8], (nb, d2, Ha, hd), d2)},
        "attn_norm": jnp.zeros((nb, d2)),
        "down": _normal(ks[9], (nb, F, d), F),
        "ffn_norm": jnp.zeros((nb, d)),
        "gate_up": _normal(ks[10], (nb, d, 2 * F), d),
    }
    calls = {"adapter_in": _normal(ks[11], (nc, d, r), d),
             "adapter_out": _normal(ks[12], (nc, r, 2 * F), r),
             "linear": _normal(ks[13], (nc, d, d), d)}
    return {"embedding": jax.random.normal(ks[4], (dims.vocab, d))
            / math.sqrt(d),
            "final_norm": jnp.zeros((d,)),
            "hybrid": calls,
            "layers": {"ssm": ssm, "ssm_norm": jnp.zeros((L, d))},
            "shared_blocks": blocks}


# -- model -------------------------------------------------------------------


def rmsnorm(x, w, eps, groups: int = 1):
    """RMS norm over each of ``groups`` equal slices of the last dim,
    times (1 + w)."""
    xg = x.reshape(x.shape[:-1] + (groups, -1))
    xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, axis=-1, keepdims=True) + eps)
    return xg.reshape(x.shape) * (1.0 + w)


def ssd(x, dt, A, Bm, Cm, q):
    """Quadratic SSD, one head at a time. x (b, s, H, P), dt (b, s, H),
    A (H,), Bm/Cm (b, s, G, N); head h uses group h // (H / G)."""
    b, s, H, P = x.shape
    R = H // Bm.shape[2]
    later = jnp.arange(s)[:, None] > jnp.arange(s)[None, :]      # t > s
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]    # t >= s

    def one(args):
        h, xh, dth, a = args                   # (b, s, P), (b, s), ()
        bb = jnp.take(Bm, h // R, axis=2)      # (b, s, N)
        cc = jnp.take(Cm, h // R, axis=2)
        da = dth * a
        # seg[t, s] = sum of da over s < r <= t, by a cumulative sum down t
        seg = jnp.cumsum(jnp.where(later, da[:, :, None], 0.0), axis=1)
        decay = jnp.where(causal, jnp.exp(seg), 0.0)      # (b, t, s)
        w = jnp.einsum("btn,bsn->bts", q(cc), q(bb)) * decay \
            * dth[:, None, :]
        return jnp.einsum("bts,bsp->btp", w, q(xh))

    y = jax.lax.map(jax.checkpoint(one),
                    (jnp.arange(H), x.transpose(2, 0, 1, 3),
                     dt.transpose(2, 0, 1), A))
    return y.transpose(1, 2, 0, 3)


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
    y = rmsnorm(y * jax.nn.silu(z), p["gate_norm"], dims.norm_eps,
                dims.n_groups)
    return q(y) @ q(p["out_proj"])


def rope(x, theta: float):
    """Rotary embedding over the whole head, halves rotated
    (``rotate_half``). x (b, s, H, hd)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(qh, kh, vh, scale):
    """One full causal softmax per head. (b, s, H, hd) each."""
    s = qh.shape[1]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one(args):
        a, b_, c = args                                   # (b, s, hd)
        sc = jnp.einsum("btk,bsk->bts", a, b_) * scale
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsk->btk", w, c)

    o = jax.lax.map(jax.checkpoint(one),
                    tuple(t.transpose(2, 0, 1, 3) for t in (qh, kh, vh)))
    return o.transpose(1, 2, 0, 3)


def shared_call(blk, call, h, e, dims: Dims, q):
    """Call of a shared block on [h, e], then the call's linear: t."""
    eps = dims.norm_eps
    x = q(rmsnorm(jnp.concatenate([h, e], -1), blk["attn_norm"], eps))
    a = blk["attn"]
    qh = rope(jnp.einsum("bsd,dhk->bshk", x, q(a["wq"])), dims.rope_theta)
    kh = rope(jnp.einsum("bsd,dhk->bshk", x, q(a["wk"])), dims.rope_theta)
    vh = jnp.einsum("bsd,dhk->bshk", x, q(a["wv"]))
    o = attention(q(qh), q(kh), q(vh), (dims.attn_head_dim / 2) ** -0.5)
    o = jnp.einsum("bshk,hkd->bsd", q(o), q(a["wo"]))
    y = q(rmsnorm(o, blk["ffn_norm"], eps))
    gu = y @ q(blk["gate_up"]) \
        + q(y @ q(call["adapter_in"])) @ q(call["adapter_out"])
    gate, up = gu[..., :dims.ffn], gu[..., dims.ffn:]
    m = q(jax.nn.gelu(gate, approximate=False) * up) @ q(blk["down"])
    return q(m) @ q(call["linear"])


def _row(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def hidden(params, tokens, dims: Dims, q=identity):
    """Final-normed hidden states (b, s, d)."""
    x = params["embedding"][tokens]
    e = x
    calls = {lid: c for c, lid in enumerate(dims.hybrid_layer_ids)}

    def mamba(x, lp, t):
        h = x if t is None else x + t
        return x + mixer(rmsnorm(h, lp["ssm_norm"], dims.norm_eps),
                         lp["ssm"], dims, q)

    def hybrid(x, lp, blk, call):
        return mamba(x, lp, shared_call(blk, call, x, e, dims, q))

    def plain(x, lp):
        return mamba(x, lp, None), None

    i = 0
    while i < dims.n_layers:
        if i in calls:
            c = calls[i]
            x = jax.checkpoint(hybrid)(
                x, _row(params["layers"], i),
                _row(params["shared_blocks"], c % dims.num_mem_blocks),
                _row(params["hybrid"], c))
            i += 1
            continue
        # a run of layers without a call, scanned
        j = next((k for k in range(i, dims.n_layers) if k in calls),
                 dims.n_layers)
        x, _ = jax.lax.scan(jax.checkpoint(plain), x, jax.tree_util.tree_map(
            lambda a: a[i:j], params["layers"]))
        i = j
    return rmsnorm(x, params["final_norm"], dims.norm_eps)


def logits(params, tokens, dims: Dims, q=identity):
    return q(hidden(params, tokens, dims, q)) @ q(params["embedding"]).T


def lm_loss(params, tokens, targets, dims: Dims, q=identity, keep=None):
    """Mean next-token cross-entropy of one worker's (batch, seq) rows;
    ``keep`` (seq,) bool, when given, limits the mean to those positions.
    The head's logits are made a quarter of the sequence at a time."""
    x = q(hidden(params, tokens, dims, q))
    emb = q(params["embedding"])
    s = x.shape[1]
    part = s // 4 if s % 4 == 0 else s

    def nll(args):
        xb, tb = args
        lg = xb @ emb.T
        return jax.nn.logsumexp(lg, axis=-1) \
            - jnp.take_along_axis(lg, tb[..., None], axis=-1)[..., 0]

    out = jax.lax.map(jax.checkpoint(nll), (
        x.reshape(x.shape[0], -1, part, x.shape[2]).transpose(1, 0, 2, 3),
        targets.reshape(targets.shape[0], -1, part).transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2).reshape(targets.shape)
    if keep is None:
        return jnp.mean(out)
    w = jnp.broadcast_to(keep, out.shape).astype(jnp.float32)
    return jnp.sum(out * w) / jnp.sum(w)


# -- the flat chunked layout of the round over mp model shards ---------------


def shard_dim(path, shape, mp: int) -> int:
    """The dim a leaf splits along over mp model shards: its largest dim
    divisible by mp (ties to the trailing dim), never the leading dim of a
    stacked collection; -1 at mp 1."""
    if mp == 1:
        return -1
    keys = [getattr(k, "key", None) for k in path]
    skip = any(k in STACKED for k in keys)
    best = -1
    for i, n in enumerate(shape):
        if skip and i == 0:
            continue
        if n > 1 and n % mp == 0 and (best < 0 or n >= shape[best]):
            best = i
    if best < 0:
        raise ValueError(f"leaf {jax.tree_util.keystr(path)} {shape} does "
                         f"not split over {mp} model shards")
    return best


class Layout:
    """Leaves split over ``mp`` model shards; section m is the raveled
    m-th slice of every leaf in pytree order, zero-padded to a whole number
    of D_c chunks rounded up to ``pad_to``; the sections one after the
    other. With a ``mesh`` (one axis), arrays in this layout are placed by
    rows over its devices, and trees by each leaf's split dim."""

    def __init__(self, tree, chunk: int, pad_to: int, mp: int = 1,
                 mesh=None):
        leaves, self.treedef = jax.tree_util.tree_flatten_with_path(tree)
        self.shapes = [tuple(x.shape) for _, x in leaves]
        self.dims = [shard_dim(p, x.shape, mp) for p, x in leaves]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.mp, self.chunk, self.mesh = mp, chunk, mesh
        self.m_sizes = [n // mp for n in self.sizes]
        self.m_offsets = [int(o) for o in
                          np.cumsum([0] + self.m_sizes[:-1])]
        self.D = sum(self.sizes)
        n = -(-sum(self.m_sizes) // chunk)
        self.n_half = -(-n // pad_to) * pad_to
        self.n_chunks = mp * self.n_half
        # each leaf's entries, contiguous, in the order ``tree_order``
        # gives them
        self.offsets = [int(o) for o in np.cumsum([0] + self.sizes[:-1])]

    def _place(self, x, spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def row_spec(self):
        return P(self.mesh.axis_names[0], None)

    def _leaf_spec(self, i):
        dim = self.dims[i]
        parts = [None] * len(self.shapes[i])
        if dim >= 0 and self.mesh is not None:
            parts[dim] = self.mesh.axis_names[0]
        return P(*parts)

    def _slice(self, leaf, i, m):
        dim = self.dims[i]
        if dim < 0:
            return leaf
        k = self.shapes[i][dim] // self.mp
        return jax.lax.slice_in_dim(leaf, m * k, (m + 1) * k, axis=dim)

    def _shard_shape(self, i):
        s = list(self.shapes[i])
        if self.dims[i] >= 0:
            s[self.dims[i]] //= self.mp
        return tuple(s)

    def to_master(self, tree):
        leaves = jax.tree_util.tree_leaves(tree)
        sec = self.n_half * self.chunk
        flat = jnp.concatenate([
            jnp.pad(jnp.concatenate(
                [self._slice(x, i, m).reshape(-1).astype(jnp.float32)
                 for i, x in enumerate(leaves)]),
                (0, sec - sum(self.m_sizes)))
            for m in range(self.mp)])
        return self._place(flat.reshape(self.n_chunks, self.chunk),
                           self.row_spec() if self.mesh else None)

    def to_tree(self, master):
        flat = master.reshape(self.mp, -1)
        out = []
        for i, (o, n) in enumerate(zip(self.m_offsets, self.m_sizes)):
            parts = [flat[m, o:o + n].reshape(self._shard_shape(i))
                     for m in range(self.mp)]
            leaf = parts[0] if self.dims[i] < 0 else \
                jnp.concatenate(parts, axis=self.dims[i])
            out.append(self._place(leaf, self._leaf_spec(i)
                                   if self.mesh else None))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def leaf_norms(self, master):
        """L2 norm of each leaf's entries, (n_leaves,)."""
        flat = master.reshape(self.mp, -1)
        return jnp.stack([
            jnp.sqrt(sum(jnp.sum(jnp.square(flat[m, o:o + n]))
                         for m in range(self.mp)))
            for o, n in zip(self.m_offsets, self.m_sizes)])

    def tree_order(self, rows: np.ndarray) -> np.ndarray:
        """(U, n_chunks * D_c) host rows in this layout -> (U, D) with each
        leaf's entries contiguous at ``offsets`` (its sections one after
        the other): what a per-leaf gap of the residual reads."""
        flat = rows.reshape(rows.shape[0], self.mp, -1)
        return np.concatenate([flat[:, m, o:o + n]
                               for o, n in zip(self.m_offsets, self.m_sizes)
                               for m in range(self.mp)], axis=1)


# -- the round ---------------------------------------------------------------


def grads(master, tokens, targets, layout: Layout, dims: Dims, q=identity,
          keep=None):
    """Per-worker losses (U,) and gradients (U, n_chunks, D_c)."""
    params = layout.to_tree(master)
    out = [jax.value_and_grad(lm_loss)(params, tokens[u], targets[u], dims,
                                       q, keep)
           for u in range(tokens.shape[0])]
    return (jnp.stack([l for l, _ in out]),
            jnp.stack([layout.to_master(g) for _, g in out]))


def codec_step(carry: Carry, g, t, key, hp: Hyper, block: int,
               parts: int = 1, spec: Optional[P] = None):
    """EF top-kappa, uplink, MAC, decode and Adam for round t (0-based),
    as ``reference.mamba2.codec_step``, with the blocks of chunk rows taken
    in ``parts`` runs side by side (one per model shard). Returns
    (carry', ghat)."""
    phi = codec.make_phi(hp.phi_seed, hp.measure, hp.chunk)
    U = g.shape[0]

    def runs(x):
        return x.reshape((parts, -1, block) + x.shape[1:])

    def up(args):
        gb, rb = args
        corrected = gb + rb
        sp = codec.topk(corrected, hp.topk)
        s, mag = codec.uplink(sp, phi)
        return s, mag, corrected - sp

    signs, mags, res = [], [], []
    for u in range(U):
        s, mag, r = jax.vmap(lambda a, b: jax.lax.map(up, (a, b)))(
            runs(g[u]), runs(carry.residual[u]))
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

    ghat = jax.vmap(lambda a, b: jax.lax.map(down, (a, b)))(
        runs(y), runs(mbar)).reshape(g.shape[1:])
    master, m, v = codec.adam(carry.master, carry.m, carry.v, t + 1, ghat,
                              hp.lr)
    return Carry(master, m, v, jnp.stack(res)), ghat
