"""What an attention site asks with and is answered with.

``GeometryKey`` is a site's geometry as ``ops/attention.select_kernel``
sees it at trace time — (heads, head_dim, q length, kv length, dtype),
with the per-shard rule of a tp-sharded site — and the ``geometry`` label
of ``cdt_attn_kernel_selected`` and of the server log's ``attention:``
line. ``KernelChoice`` is the answer: a tier and the blocks its call runs.
Nothing here chooses: the one rule is ``ops/attention.policy_choice``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# the tiers of the bidirectional dispatch: ``packed`` ([B, N, H·D] native
# layout walked in 128-lane head groups), ``bh`` (the classic [B·H, N, D]
# call), ``xla`` (the fused XLA lowering)
TIERS = ("packed", "bh", "xla")
# kernels that are no tier of the bidirectional dispatch (no policy arm
# chooses them) but report themselves the same way: the blocked causal
# kernels of a chunked prefill (ops/flash_latent.py), over a latent cache,
# over one shared key/value head, and over grouped key/value heads (whole,
# or the band of a window layer), and the kernels over selected keys
REPORTED_TIERS = TIERS + ("latent_causal", "shared_kv_causal", "gqa_causal",
                          "gqa_window", "block_select", "index_select")

_DTYPE_NAMES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
                "bf16": "bf16", "f32": "f32", "f16": "f16"}


def dtype_name(dtype) -> str:
    """Canonical short dtype tag of a geometry label ('bf16', 'f32', ...).
    Accepts numpy/jax dtypes, scalar types (``jnp.bfloat16``) and
    strings; already-short tags pass through."""
    import numpy as np

    try:
        name = np.dtype(dtype).name
    except TypeError:
        name = getattr(dtype, "name", None) or str(dtype)
    return _DTYPE_NAMES.get(name, name)


def itemsize_of(dtype) -> int:
    """Operand byte width for the VMEM working-set model. One
    definition — the dispatcher and the kernels' legality checks both
    key on it, and a drift between them would approve blocks the
    kernel can't fit."""
    return 4 if dtype_name(dtype) == "f32" else 2


def seq_bucket(n: int) -> int:
    """Next power of two ≥ n, floored at 128: the cardinality rule of a
    metric LABEL (SDXL 4096 → 4096, WAN 14040 → 16384, a 77-token text
    context → 128), so that a resolution family is one series of
    ``cdt_attn_kernel_selected`` and one ``attention:`` line. Nothing is
    CHOSEN by bucket: the policy's floors and the packed blocks read the
    exact lengths."""
    b = 128
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass(frozen=True)
class GeometryKey:
    """One attention geometry as the dispatcher sees it at trace time."""

    num_heads: int
    head_dim: int
    q_bucket: int
    kv_bucket: int
    dtype: str = "bf16"

    @classmethod
    def from_shape(cls, num_heads: int, head_dim: int, q_len: int,
                   kv_len: int, dtype="bfloat16") -> "GeometryKey":
        return cls(num_heads=int(num_heads), head_dim=int(head_dim),
                   q_bucket=seq_bucket(int(q_len)),
                   kv_bucket=seq_bucket(int(kv_len)),
                   dtype=dtype_name(dtype))

    def key_str(self) -> str:
        """The telemetry geometry label (``h10.d64.q4096.kv4096.bf16``)."""
        return (f"h{self.num_heads}.d{self.head_dim}.q{self.q_bucket}"
                f".kv{self.kv_bucket}.{self.dtype}")

    def shard(self, tp: int) -> "GeometryKey":
        """The PER-SHARD geometry a tp-sharded site executes: the
        Megatron column split lands on the head axis, so each shard
        runs H/tp heads of the same sequence. The policy and the
        legality checks must read THIS geometry — blocks chosen for the
        full H can be illegal (or slow) at H/tp. Indivisible head counts
        don't shard (the TP placement rules fall back to replication
        there too), so the key is unchanged."""
        if tp <= 1 or self.num_heads % tp:
            return self
        return dataclasses.replace(self, num_heads=self.num_heads // tp)


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """A resolved kernel config: what ``full_attention`` should run."""

    tier: str
    block_q: Optional[int] = None      # None: tier has no blocks (xla),
    block_k: Optional[int] = None      # or packed derives them (shape)
    source: str = "default"            # default (the policy) | env
    reason: str = ""

    def __post_init__(self):
        if self.tier not in REPORTED_TIERS:
            raise ValueError(f"unknown kernel tier {self.tier!r}; "
                             f"have {REPORTED_TIERS}")
