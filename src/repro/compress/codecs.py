"""The codec family: identity, uniform int quantizer, top-k, fp8 cast.

Every codec is a frozen (hashable) dataclass so it can be a field of the
frozen ``CommModel`` and be closed over by jitted step functions as static
data.  Each exposes two faces:

- the **numerics path** — ``encode``/``decode`` (and their fused
  composition ``apply``) are jit-able JAX transforms that simulate the
  lossy channel in the literal split-learning dataflow.  Stochastic
  rounding is driven by explicit PRNG keys (``repro.utils.prng``-style),
  so runs are reproducible and deterministic codecs simply ignore the key;
- the **byte path** — ``payload_bits(n_elements)`` is what one encoded
  tensor costs on the wire, which is what ``repro.core.comm`` charges
  instead of the hardcoded ``(omega + 1)`` bits per element.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Codec:
    """Common API: a lossy tensor channel with exact byte accounting."""

    name = "codec"

    def __post_init__(self):
        # codecs ride inside the frozen CommModel and are closed over by
        # jitted step functions as static data — every field must hash NOW,
        # not fail later inside jax's static-arg machinery with a message
        # that points nowhere near the offending codec
        for f in dataclasses.fields(self):
            value = getattr(self, f.name)
            try:
                hash(value)
            except TypeError:
                raise TypeError(
                    f"{type(self).__name__}.{f.name} must be hashable "
                    f"(codecs are static data under jit); got "
                    f"{type(value).__name__}: {value!r}") from None

    def payload_bits(self, n_elements: int) -> int:
        raise NotImplementedError

    def encode(self, key, x):
        raise NotImplementedError

    def decode(self, enc):
        raise NotImplementedError

    def apply(self, key, x):
        """The round trip the receiver sees: decode(encode(x))."""
        return self.decode(self.encode(key, x))


@dataclass(frozen=True)
class IdentityCodec(Codec):
    """Full-precision passthrough: today's (omega+1)-bit accounting, and a
    numerics path that is bit-identical to no codec at all (the regression
    anchor for the whole subsystem).

    ``bits_per_element=None`` (the default) DEFERS the byte accounting to
    the consuming ``CommModel``'s own ``omega+1`` — so one identity codec
    is exact for the CNN (omega=32) and the LM (omega=16) alike; pin a
    width explicitly only for standalone payload math."""

    bits_per_element: int | None = None

    name = "fp32"

    def payload_bits(self, n_elements: int) -> int:
        if self.bits_per_element is None:
            raise ValueError(
                "this IdentityCodec defers its width to the comm model's "
                "omega; construct it with an explicit bits_per_element (or "
                "get_codec('fp32', omega=...)) for standalone payload math")
        return n_elements * self.bits_per_element

    def encode(self, key, x):
        return (x,)

    def decode(self, enc):
        return enc[0]

    def apply(self, key, x):
        return x


@dataclass(frozen=True)
class UniformQuantCodec(Codec):
    """Symmetric uniform quantizer to ``bits``-bit integers with per-tensor
    absmax scaling and stochastic rounding (the FedLite-style smashed-data
    quantizer).  The hot ``apply`` path is the fused Pallas kernel in
    ``repro.kernels.quantize``; ``encode``/``decode`` expose the integer
    payload itself.  int4 values travel packed (4 bits each on the wire)
    but are stored in int8 lanes on chip."""

    bits: int = 8
    stochastic: bool = True
    scale_bits: int = 32             # one fp32 scale per tensor

    def __post_init__(self):
        super().__post_init__()
        # the integer payload lives in int8 lanes (encode) and the kernel
        # clips to [-qmax, qmax]; wider widths would silently wrap
        if not 2 <= self.bits <= 8:
            raise ValueError(f"uniform quantizer supports 2..8 bits, got "
                             f"{self.bits}")

    @property
    def name(self) -> str:           # type: ignore[override]
        return f"int{self.bits}"

    @property
    def qmax(self) -> int:
        return 2 ** (self.bits - 1) - 1

    def payload_bits(self, n_elements: int) -> int:
        return n_elements * self.bits + self.scale_bits

    def _uniforms(self, key, shape):
        if self.stochastic:
            return jax.random.uniform(key, shape, jnp.float32)
        return jnp.full(shape, 0.5, jnp.float32)

    def encode(self, key, x):
        from repro.kernels.quantize.ops import tensor_scale
        scale = tensor_scale(x, self.qmax)[0, 0]
        inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
        q = jnp.floor(x.astype(jnp.float32) * inv + self._uniforms(key, x.shape))
        q = jnp.clip(q, -self.qmax, self.qmax).astype(jnp.int8)
        return (q, scale)

    def decode(self, enc):
        q, scale = enc
        return q.astype(jnp.float32) * scale

    def apply(self, key, x):
        from repro.kernels.quantize.ops import quantize_dequantize
        return quantize_dequantize(x, key, bits=self.bits,
                                   stochastic=self.stochastic)


@dataclass(frozen=True)
class TopKCodec(Codec):
    """Magnitude top-k sparsification over the flattened tensor: ship the
    k = max(1, frac * n) largest-|x| values plus their indices; the receiver
    scatters into zeros.  Index bits are charged at ceil(log2 n) each —
    sparsity is only a win once value+index bits undercut dense payloads."""

    frac: float = 0.05
    value_bits: int = 32

    @property
    def name(self) -> str:           # type: ignore[override]
        return f"topk{self.frac:g}"

    def k_for(self, n_elements: int) -> int:
        return max(1, int(n_elements * self.frac))

    def payload_bits(self, n_elements: int) -> int:
        k = self.k_for(n_elements)
        idx_bits = math.ceil(math.log2(max(n_elements, 2)))
        return k * (self.value_bits + idx_bits)

    def encode(self, key, x):
        flat = x.reshape(-1)
        k = self.k_for(flat.shape[0])
        _, idx = jax.lax.top_k(jnp.abs(flat.astype(jnp.float32)), k)
        return (flat[idx], idx, x.shape)

    def decode(self, enc):
        vals, idx, shape = enc
        n = math.prod(shape)
        return jnp.zeros(n, vals.dtype).at[idx].set(vals).reshape(shape)


@dataclass(frozen=True)
class Fp8Codec(Codec):
    """Per-tensor-scaled cast to float8 (e4m3): x -> (x / s) as fp8, with
    s = absmax / 448 so the tensor spans the fp8 dynamic range.  8 bits per
    element plus one fp32 scale; rounding is the dtype cast's
    (deterministic), so the key is ignored."""

    scale_bits: int = 32

    name = "fp8"

    def payload_bits(self, n_elements: int) -> int:
        return n_elements * 8 + self.scale_bits

    @staticmethod
    def _dtype():
        dt = getattr(jnp, "float8_e4m3fn", None)
        if dt is None:                       # gate: very old jax builds
            raise NotImplementedError(
                "this jax build has no float8_e4m3fn dtype; use the int8 "
                "codec instead")
        return dt

    def encode(self, key, x):
        dt = self._dtype()
        absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
        scale = jnp.where(absmax > 0, absmax / 448.0, 1.0)
        return ((x.astype(jnp.float32) / scale).astype(dt), scale)

    def decode(self, enc):
        y, scale = enc
        return y.astype(jnp.float32) * scale


# --------------------------------------------------------------------------
@dataclass(frozen=True)
class LinkCodecs:
    """Which codec each of the three Remark-1 payloads travels through.
    ``None`` means the legacy full-precision ``(omega+1)``-bit path."""

    activations: Codec | None = None   # cut-layer o_fp, client -> ES
    gradients: Codec | None = None     # cut-layer o_bp, ES -> client
    offload: Codec | None = None       # client-block params at round edges

    def __post_init__(self):
        # same static-data contract as Codec.__post_init__: the triple is a
        # CommModel field and a jit static arg, so reject non-codec (and
        # thus possibly unhashable) payloads at construction
        for f in dataclasses.fields(self):
            value = getattr(self, f.name)
            if value is not None and not isinstance(value, Codec):
                raise TypeError(
                    f"LinkCodecs.{f.name} must be a Codec or None (static "
                    f"data under jit); got {type(value).__name__}: "
                    f"{value!r}")

    def is_lossless(self) -> bool:
        return all(c is None or isinstance(c, IdentityCodec)
                   for c in (self.activations, self.gradients, self.offload))


CODEC_NAMES = ("fp32", "int8", "int4", "topk", "fp8")


def get_codec(name: str, *, bits: int | None = None, topk_frac: float = 0.05,
              omega: int | None = None, stochastic: bool = True) -> Codec:
    """Codec presets by name (``bits`` overrides the int quantizer width).

    ``omega`` only pins the identity codec's width; left None, the identity
    codec defers to whatever ``omega`` the consuming CommModel carries."""
    if name in ("fp32", "identity"):
        return IdentityCodec(
            bits_per_element=None if omega is None else omega + 1)
    if name in ("int8", "int4"):
        return UniformQuantCodec(bits=bits or int(name[3:]),
                                 stochastic=stochastic)
    if name == "topk":
        return TopKCodec(frac=topk_frac)
    if name == "fp8":
        return Fp8Codec()
    raise ValueError(f"unknown codec {name!r}; one of {CODEC_NAMES}")


def link_codecs(name: str, **kw) -> LinkCodecs:
    """The same preset codec on all three links (the common scenario)."""
    c = get_codec(name, **kw)
    return LinkCodecs(activations=c, gradients=c, offload=c)
