"""Architecture configuration schema.

One instance per assigned architecture lives in ``repro/configs/<id>.py``.
Layer layout = head (unrolled) + n_blocks x pattern (lax.scan) + tail
(unrolled); ``n_layers`` must equal len(head) + n_blocks*len(pattern) +
len(tail).

Layer kinds:
  'global'        full-attention block (GQA or MLA) + FFN (MoE if cfg.moe)
  'global_dense'  like 'global' but always a dense FFN (DeepSeek layer 0)
  'local'         sliding-window attention block + FFN
  'mamba'         Mamba2 SSD block
  'shared'        zamba2-style shared transformer block (one param set,
                  reused at every occurrence; per-occurrence KV cache)
"""
from __future__ import annotations

import dataclasses

from .layers import YarnConfig
from .moe import MoEConfig
from .ssm import SSMConfig


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora: int | None              # None: one wq, no query compression
    kv_lora: int
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    family: str = "lm"                     # 'lm' | 'encdec'
    head_dim: int | None = None
    head: tuple[str, ...] = ()
    pattern: tuple[str, ...] = ("global",)
    tail: tuple[str, ...] = ()
    window: int | None = None
    attn_softcap: float | None = None
    logit_softcap: float | None = None
    rope_theta: float = 10_000.0
    yarn: YarnConfig | None = None         # YaRN rotary (MLA's rope dims)
    norm_eps: float = 1e-6
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    enc_layers: int = 0
    frontend: str | None = None            # 'audio' | 'vision' (stub embeddings)
    frontend_tokens: int = 0
    embed_scale: bool = False              # gemma: embeddings * sqrt(d_model)
    dtype: str = "bfloat16"
    remat: str = "full"                    # 'full' | 'none'
    long_context: bool = False             # may run the long_500k shape
    quant_kv: str = "none"                 # 'none' | 'dynamic' (int8 KV cache)
    loss_chunk: int = 512

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_blocks(self) -> int:
        n = self.n_layers - len(self.head) - len(self.tail)
        assert n % len(self.pattern) == 0, (
            f"{self.name}: {n} layers not divisible by pattern {self.pattern}")
        return n // len(self.pattern)

    def validate(self) -> "ArchConfig":
        _ = self.n_blocks
        if self.moe:
            assert self.moe.n_experts % 1 == 0
            assert 0 <= self.moe.held_offset and (
                self.moe.held_offset + self.moe.held <= self.moe.n_experts), (
                f"{self.name}: experts {self.moe.held_offset}.."
                f"{self.moe.held_offset + self.moe.held - 1} held of "
                f"{self.moe.n_experts}")
        if self.mla is not None and self.quant_kv != "none":
            # the latent cache has no int8 layout: refuse, never ignore
            raise ValueError(f"{self.name}: quant_kv={self.quant_kv!r} is not "
                             "supported with MLA layers (the latent cache "
                             "stays in the model dtype)")
        return self


def reduced(cfg: ArchConfig, **overrides) -> ArchConfig:
    """Smoke-test variant: same family/topology, tiny dims."""
    base = dict(
        n_layers=len(cfg.head) + 2 * len(cfg.pattern) + len(cfg.tail),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2),
        d_ff=128,
        vocab=512,
        head_dim=16,
        enc_layers=min(cfg.enc_layers, 2),
        frontend_tokens=min(cfg.frontend_tokens, 8),
        window=min(cfg.window, 16) if cfg.window else None,
        remat="none",
        loss_chunk=16,
        # CPU-executable smoke configs: the CPU runtime lacks the
        # bf16 x bf16 -> f32 dot thunk the TPU-target bf16 path uses.
        dtype="float32",
    )
    if cfg.moe:
        base["moe"] = dataclasses.replace(
            cfg.moe, n_experts=8, top_k=min(cfg.moe.top_k, 2), d_ff_expert=32,
            d_ff_dense=64 if (cfg.moe.n_shared or cfg.moe.dense_residual) else 0,
            capacity_factor=8.0,  # avoid capacity drops in tiny smoke tests
            n_held=cfg.moe.n_held and 2, held_offset=0)
    if cfg.mla:
        base["mla"] = MLAConfig(q_lora=32 if cfg.mla.q_lora else None,
                                kv_lora=32, qk_nope=16, qk_rope=8, v_head=16)
    if cfg.ssm:
        base["ssm"] = SSMConfig(d_model=64, d_state=16, head_dim=16, expand=2,
                                d_conv=4, chunk=16)
    base.update(overrides)
    return dataclasses.replace(cfg, **base).validate()
