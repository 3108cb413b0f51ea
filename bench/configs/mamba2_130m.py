"""Mamba2-130M: the program's model step, the benchmark's weights, and a plain
float32 reference, for the sizes in ``mamba2_130m.json`` beside this file.

The reference imports nothing of the program.  It follows the Mamba-2 paper
(arXiv:2405.21060): per layer an RMSNorm, one input projection into
(z, x|B|C, dt), a depthwise causal convolution of width ``d_conv`` with a
SiLU over x|B|C, the SSD scan ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``,
``y_t = C_t h_t + D x_t`` (one B and C shared by all heads, ngroups = 1),
a gate ``y * silu(z)``, an RMSNorm and the output projection, added to the
residual; then a final RMSNorm and logits against the tied embedding.  The
scan is the paper's chunked "minimal SSD" (stable segment sums, chunk
states, an exact chunk-to-chunk decay matrix), which shares no arithmetic
with the program's kernel or its jnp path.

Parameter names and layouts are the program's interface (the weights are
handed to it): ``conv_w[i]`` multiplies the input ``d_conv - 1 - i`` steps
back, ``in_proj`` splits as z | x | B | C | dt.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

SIZES = json.load(open(os.path.join(os.path.dirname(__file__), "mamba2_130m.json")))


class Model:
    """One configuration of the family, from its sizes file."""

    batch_keys = ("tokens", "labels")

    def __init__(self, sizes: dict = SIZES):
        self.s = dict(sizes)
        s = self.s
        self.L, self.D, self.V = s["n_layer"], s["d_model"], s["vocab_size"]
        self.N, self.P, self.W = s["d_state"], s["headdim"], s["d_conv"]
        self.d_inner = s["expand"] * self.D
        self.H = self.d_inner // self.P
        self.conv_ch = self.d_inner + 2 * s["ngroups"] * self.N
        self.d_proj = 2 * self.d_inner + 2 * s["ngroups"] * self.N + self.H
        self.Q = s["chunk_size"]
        self.eps = s["rms_norm_eps"]
        self.dtype = jnp.dtype(s["dtype"])
        self._prog_cfg = None

    # ---------------------------------------------------------- program
    def program_config(self):
        """The program's ModelConfig for these sizes, kernels on."""
        if self._prog_cfg is None:
            from repro.configs.base import ModelConfig

            s = self.s
            self._prog_cfg = ModelConfig(
                name=s["name"], family="ssm", num_layers=self.L, d_model=self.D,
                vocab_size=self.V, ssm_state=self.N, ssm_head_dim=self.P,
                ssm_expand=s["expand"], ssm_conv=self.W, ssm_chunk=self.Q,
                ssm_groups=s["ngroups"], tie_embeddings=s["tie_embeddings"],
                norm_eps=self.eps, dtype=s["dtype"], remat=s["remat"],
                use_pallas=s["ssd_kernel"] == "pallas",
            )
        return self._prog_cfg

    def program_loss(self, params, batch):
        """The system under test: the program's loss of one batch."""
        from repro.models import api

        return api.loss_fn(params, batch, self.program_config())[0]

    def check_structure(self, params) -> None:
        """Raise if ``params`` is not the tree of shapes the program's model
        declares (a mismatch would hand the program weights it misreads)."""
        from repro.models import api
        from repro.models.module import abstract_params

        want = abstract_params(api.model_meta(self.program_config()))
        got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
        want = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
        if got != want:
            raise ValueError(f"weights {got} do not match the program's tree {want}")

    # ---------------------------------------------------------- weights
    def init_weights(self, key):
        """The cell's weights from ``key``, made on the device in one call,
        in the dtypes they are served in (A_log and dt_bias in float32)."""
        L, D, V, H, W = self.L, self.D, self.V, self.H, self.W
        dt = self.dtype

        @jax.jit
        def make(key):
            k = jax.random.split(key, 6)
            nrm = lambda kk, shape, std: (std * jax.random.normal(kk, shape, jnp.float32)).astype(dt)
            dt0 = jax.random.uniform(k[5], (L, H), jnp.float32, 1e-3, 1e-1)
            return {
                "embed": nrm(k[0], (V, D), 1.0 / np.sqrt(D)),
                "blocks": {
                    "in_proj": nrm(k[1], (L, D, self.d_proj), 1.0 / np.sqrt(D)),
                    "conv_w": nrm(k[2], (L, W, self.conv_ch), 1.0 / np.sqrt(W)),
                    "conv_b": jnp.zeros((L, self.conv_ch), dt),
                    "A_log": jnp.log(jax.random.uniform(k[3], (L, H), jnp.float32, 1.0, 16.0)),
                    "D": jnp.ones((L, H), dt),
                    "dt_bias": jnp.log(jnp.expm1(dt0)),
                    "norm": jnp.ones((L, self.d_inner), dt),
                    "out_proj": nrm(k[4], (L, self.d_inner, D), 1.0 / np.sqrt(self.d_inner)),
                    "pre_norm": jnp.ones((L, D), dt),
                },
                "final_norm": jnp.ones((D,), dt),
            }

        return make(key)

    # ---------------------------------------------------------- reference
    # The control is the reference one precision step below the
    # configuration's everywhere: matmul operands and the weights handed
    # between calls in float8 e4m3 (for bfloat16), the master copy inside a
    # call in bfloat16 (for float32).
    def master_dtype(self, precision: str):
        return jnp.bfloat16 if precision == "float8_e4m3" else jnp.float32

    def store(self, w, precision: str):
        """A leaf as the weights handed to the next call hold it."""
        return _fp8(w.astype(jnp.float32)) if precision == "float8_e4m3" else w

    def ref_loss(self, params, batch, precision: str = "float32"):
        """Plain next-token cross entropy of the model; ``precision`` is
        "float32", or the control's "float8_e4m3": every matmul operand
        rounded to e4m3 under a per-tensor scale on the forward pass,
        accumulation and gradients in float32."""
        f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
        p = f32(params)
        q = _fp8 if precision == "float8_e4m3" else (lambda a: a)
        mm = lambda a, b: jnp.einsum("...i,ij->...j", q(a), q(b))
        x = p["embed"][batch["tokens"]]

        @jax.checkpoint
        def layer(x, lp):
            return x + self._mixer(lp, _rms(x, lp["pre_norm"], self.eps), mm), None

        x, _ = jax.lax.scan(layer, x, p["blocks"])
        x = _rms(x, p["final_norm"], self.eps)
        logits = mm(x, p["embed"].T)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
        return jnp.mean(nll)

    def _mixer(self, lp, h, mm):
        Bsz, S, _ = h.shape
        di, N, H, P, W = self.d_inner, self.N, self.H, self.P, self.W
        proj = mm(h, lp["in_proj"])
        z, xbc, dt_raw = proj[..., :di], proj[..., di:di + self.conv_ch], proj[..., di + self.conv_ch:]
        pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
        conv = lp["conv_b"] + sum(pad[:, i:i + S] * lp["conv_w"][i] for i in range(W))
        xbc = jax.nn.silu(conv)
        xs = xbc[..., :di].reshape(Bsz, S, H, P)
        Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
        dt = jax.nn.softplus(dt_raw + lp["dt_bias"])
        A = -jnp.exp(lp["A_log"])
        y = _ssd_minimal(xs, dt, A, Bm, Cm, self.Q) + xs * lp["D"][:, None]
        y = y.reshape(Bsz, S, di) * jax.nn.silu(z)
        return mm(_rms(y, lp["norm"], self.eps), lp["out_proj"])

    # ---------------------------------------------------------- counts
    def flops(self, rows: int, seq: int) -> dict:
        """Model FLOPs of one update's gradient (forward + backward = 3x the
        forward's matmul work; recompute not counted) and of one eval
        forward, for ``rows`` x ``seq`` tokens.  Per token and layer: the
        input and output projections, the depthwise convolution, and the
        chunked SSD's C.B scores (shared by the heads), its (Q, Q) x (Q, P)
        product, and the chunk state in and out (N x P per head each); the
        logits against the tied embedding once per token."""
        D, di, Q, N, P, H = self.D, self.d_inner, self.Q, self.N, self.P, self.H
        ssd = 2 * Q * N + H * (2 * Q * P + 4 * N * P)
        layer = 2 * D * self.d_proj + 2 * self.W * self.conv_ch + ssd + 2 * di * D
        fwd = rows * seq * (self.L * layer + 2 * D * self.V)
        return {"update": 3.0 * fwd, "forward": float(fwd)}

    def ssd_call_shape(self, rows: int, seq: int) -> dict:
        """Shapes of one call of the program's SSD scan kernel in this model."""
        return {"B": rows, "S": seq, "H": self.H, "P": self.P, "N": self.N, "Q": self.Q,
                "x_bytes": self.dtype.itemsize, "bc_bytes": self.dtype.itemsize}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _fp8(a):
    """Round to float8 e4m3 (4 exponent, 3 mantissa bits) under a per-tensor
    scale that maps |a|max to the format's largest normal, 240, on the forward
    pass; the gradient passes straight through in float32.  `reduce_precision`
    does the rounding: the TPU compiler drops a cast to float8 and back as
    excess precision, which left an earlier form of this control unrounded."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(a)) / 240.0 + 1e-30)
    q = jax.lax.reduce_precision(a / s, exponent_bits=4, mantissa_bits=3) * s
    return a + jax.lax.stop_gradient(q - a)


def _segsum(a):
    """(..., T) -> (..., T, T): out[i, j] = sum_{j < l <= i} a_l for i >= j,
    -inf above the diagonal; summed along the masked copy, not as a
    difference of prefix sums."""
    T = a.shape[-1]
    rep = jnp.broadcast_to(a[..., :, None], a.shape + (T,))
    low = np.tril(np.ones((T, T), bool), -1)
    cs = jnp.cumsum(jnp.where(low, rep, 0.0), axis=-2)
    return jnp.where(np.tril(np.ones((T, T), bool)), cs, -jnp.inf)


def _ssd_minimal(x, dt, A, Bm, Cm, Q):
    """Chunked SSD of the Mamba-2 paper, zero initial state.
    x (b, s, h, p), dt (b, s, h), A (h,), Bm/Cm (b, s, n) -> y (b, s, h, p)."""
    b, s, h, p = x.shape
    n, c = Bm.shape[-1], s // Q
    X = (x * dt[..., None]).reshape(b, c, Q, h, p)
    a = (dt * A).reshape(b, c, Q, h).transpose(0, 3, 1, 2)      # (b, h, c, l)
    Bc, Cc = Bm.reshape(b, c, Q, n), Cm.reshape(b, c, Q, n)
    acs = jnp.cumsum(a, axis=-1)
    Lm = jnp.exp(_segsum(a))                                     # (b, h, c, l, l)
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, Lm, X)
    decay = jnp.exp(acs[..., -1:] - acs)                         # (b, h, c, l)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(acs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, states, jnp.exp(acs))
    return (y_diag + y_off).reshape(b, s, h, p)
