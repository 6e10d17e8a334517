"""Model configuration (reference: ``models/config.py:53`` ModelConfig).

Presets cover the reference's demo models (Qwen3 dense family,
``docs/getting-started/e2e/e2e_dense.md``) plus a tiny config for the
CPU-mesh test battery.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 151936
    hidden_size: int = 4096
    intermediate_size: int = 12288
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1_000_000.0
    max_position_embeddings: int = 40960
    tie_word_embeddings: bool = False
    model_name: str = "qwen3"
    # MoE fields (0 experts = dense; reference: models/qwen_moe.py)
    num_experts: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    # Hybrid linear-attention fields (Qwen3-Next: GDN gated-delta-rule
    # layers with a full-attention layer every `full_attn_interval`;
    # 0 GDN heads = pure full attention). The reference ships the GDN
    # kernel (``kernels/nvidia/gdn.py``) for this family.
    # Attention projection biases (Seed-OSS / Qwen2-style checkpoints;
    # Qwen3 family is bias-free) and the Qwen3 per-head q/k RMS norm
    # (absent in Seed-OSS/llama-style models).
    attention_bias: bool = False
    qk_norm: bool = True
    gdn_num_heads: int = 0          # value heads (HF linear_num_value_heads)
    # Key heads may differ from value heads in real Qwen3-Next configs
    # (HF linear_num_key_heads); 0 means "same as gdn_num_heads". The
    # in-framework GDN family uses equal counts; a future HF hybrid
    # mapper needs the split (ADVICE r4).
    gdn_num_key_heads: int = 0
    gdn_head_dim_k: int = 128
    gdn_head_dim_v: int = 128
    full_attn_interval: int = 4
    # HF-faithful Qwen3-Next cell fields. gdn_conv_kernel > 0 selects
    # the checkpoint-compatible GatedDeltaNet parameterization (short
    # causal depthwise conv + z-gated RMSNorm + A_log/dt_bias decay,
    # HF ``linear_conv_kernel_dim``); 0 keeps the in-framework
    # simplified cell (wg/g_bias gates, no conv).
    gdn_conv_kernel: int = 0
    # Qwen3-Next full-attention extras: per-head sigmoid output gate
    # (q_proj emits [q | gate]) and partial RoPE (rotary on the first
    # ``partial_rotary_factor`` fraction of head_dim).
    attn_gate: bool = False
    partial_rotary_factor: float = 1.0
    # Qwen3-Next MoE shared expert (0 = none).
    shared_expert_intermediate_size: int = 0
    # Qwen3-Next RMSNorms are zero-centered ((1+w)·x̂, Gemma-style).
    # Runtime layers always compute standard w·x̂ — the HF mapper folds
    # the +1 into the stored weights at load time under this flag.
    norm_zero_centered: bool = False
    # Latent attention (``kv_lora_rank`` > 0; models/latent_moe.py):
    # queries through a bottleneck of ``q_lora_rank``, keys and values
    # expanded from one cached latent of ``kv_lora_rank`` values beside
    # one roped key of ``qk_rope_head_dim`` that every head shares.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope (``rope_factor`` > 1; layers/rope.py): frequencies
    # blended between ``f`` and ``f / rope_factor`` by how often a
    # wavelength fits ``rope_original_max_position``; the softmax scale
    # carries ``mscale_all_dim``'s factor squared; a query is scaled by
    # ``1 + rope_query_scale_beta * ln(1 + pos // original)``.
    rope_factor: float = 1.0
    rope_original_max_position: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rope_query_scale_beta: float = 0.0
    routed_scaling_factor: float = 1.0
    # One chip's share of an expert-parallel deployment, with no peer
    # here: the router is ``num_experts`` wide, the weights of
    # ``num_held_experts`` of them, from ``first_held_expert`` on, are
    # held (0 = all), and the others' part of the result is left out
    # (layers/ep_moe.py ``fwd_held``).
    first_held_expert: int = 0
    num_held_experts: int = 0
    # The expert layer's other forms (layers/ep_moe.py): ``"sigmoid"``
    # scores every expert on its own and a correction bias, a leaf of
    # the layer, is added for the SELECTION alone (``route``);
    # ``"relu2"`` experts are up, squared ReLU, down, with no gate;
    # ``moe_latent_size`` > 0 is the width the routed experts work in,
    # a latent the layer projects into and out of.
    moe_scoring: str = "softmax"
    moe_act: str = "swiglu"
    moe_latent_size: int = 0
    # A layer is a mixer OR a feed-forward part (models/mamba_moe.py):
    # ``layer_pattern`` gives each layer one letter, ``M`` a Mamba-2
    # mixer, ``E`` an expert layer, ``*`` attention; "" = every layer
    # the family's one block. A Mamba-2 layer has ``mamba_num_heads``
    # heads of ``mamba_head_dim``, B and C in ``mamba_n_groups`` groups
    # of ``ssm_state_size``, a causal depthwise convolution of
    # ``mamba_conv_kernel`` taps before them, and scans in chunks of
    # ``mamba_chunk_size`` rows; it keeps a state and the convolution's
    # tail a SEQUENCE, and no pages.
    layer_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 64
    mamba_n_groups: int = 8
    ssm_state_size: int = 128
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128
    # Layers applied several times (models/looped.py): the stack runs
    # ``num_passes`` times over the SAME weights, the final norm after
    # every pass, and each pass of a layer keeps pages of its own; an
    # exit gate scores every pass, and a row's logits are those of the
    # first pass at which the cumulated exit probability reaches
    # ``exit_threshold`` (1.0: the last). ``post_norm``: a second
    # RMSNorm AFTER each sublayer, before its residual is added.
    num_passes: int = 1
    exit_threshold: float = 1.0
    post_norm: bool = False
    # Window and global attention mixed (models/window_moe.py):
    # ``attn_pattern`` gives each layer one letter, ``L`` a layer whose
    # row ``i`` reads keys ``i - sliding_window < j <= i`` (and keeps, a
    # sequence, a bounded ring of pages), ``G`` one that reads every
    # key; "" = every layer reads every key. The first
    # ``first_dense_layers`` layers have a dense FFN of
    # ``intermediate_size``, the others routed experts.
    attn_pattern: str = ""
    sliding_window: int = 0
    first_dense_layers: int = 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def num_moe_layers(self) -> int:
        """Layers with routed experts."""
        if self.layer_pattern:
            return self.layer_pattern.count("E")
        if not self.is_moe:
            return 0
        return self.num_hidden_layers - self.first_dense_layers

    @property
    def num_paged_layers(self) -> int:
        """Layers of the pool: every application of a layer that keeps
        pages of keys keeps its own."""
        if self.layer_pattern:
            return self.layer_pattern.count("*")
        if self.attn_pattern:      # the window layers keep a pool apart
            return self.attn_pattern.count("G")
        return self.num_hidden_layers * self.num_passes

    @property
    def num_window_layers(self) -> int:
        """Layers that keep a window of positions, and a ring of pages
        a sequence in a pool of their own."""
        return self.attn_pattern.count("L")

    @property
    def is_hybrid(self) -> bool:
        return self.gdn_num_heads > 0

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def held_experts(self) -> int:
        return self.num_held_experts or self.num_experts

    @property
    def gdn_num_kh(self) -> int:
        """Key-head count (0 in the config means 'same as value
        heads', the in-framework family's shape)."""
        return self.gdn_num_key_heads or self.gdn_num_heads

    def kv_cache_plan(self, *, max_len: int, page: int,
                      num_slots: int, tp: int = 1,
                      dtype_bytes: int = 4,
                      kv_dtype: str = "bf16") -> dict:
        """Serving pool sizing off the model geometry — what the
        serving subsystem allocates from this config: pages per
        block-table row, pool pages for full residency (+1 reserved
        scratch page), and the per-rank HBM bytes of K+V pools.
        ``tp`` divides the KV heads (each rank holds its heads' pages,
        the same placement as the dense cache).

        ``kv_dtype="int8"|"fp8"`` plans a PER-PAGE QUANTIZED pool:
        storage at 1 byte/element plus one fp32 scale per (layer,
        page, kv_head) per K/V pool. The plan then also reports
        ``native_page_bytes_per_rank`` (what the page would cost
        unquantized at ``dtype_bytes``), ``bytes_per_token``, and
        ``capacity_ratio_vs_native`` — the 2–4x more-pages-per-HBM-GB
        the quantization buys at fixed pool bytes."""
        if max_len % page:
            raise ValueError(f"page={page} must divide max_len="
                             f"{max_len}")
        from triton_dist_tpu.serving.blocks import kv_quant_spec

        qdtype, _ = kv_quant_spec(kv_dtype)
        kv_loc = max(self.num_key_value_heads // tp, 1)
        p_max = max_len // page
        num_pages = 1 + num_slots * p_max
        if self.is_latent:
            # One array of [latent | roped key] a token a layer, no
            # heads to divide: every rank keeps it whole.
            if qdtype is not None:
                raise ValueError("the latent pool is not quantized: "
                                 f"kv_dtype={kv_dtype!r}")
            page_bytes = (self.num_paged_layers * page * dtype_bytes
                          * (self.kv_lora_rank + self.qk_rope_head_dim))
            return {
                "page": page, "p_max": p_max, "num_pages": num_pages,
                "kv_heads_loc": 0, "kv_dtype": "bf16",
                "page_bytes_per_rank": page_bytes,
                "native_page_bytes_per_rank": page_bytes,
                "pool_bytes_per_rank": page_bytes * num_pages,
                "bytes_per_token": page_bytes / page,
                "capacity_ratio_vs_native": 1.0,
                "tokens_per_page": page,
            }
        native_bytes = (self.num_paged_layers * kv_loc * page
                        * self.head_dim * dtype_bytes)
        if qdtype is None:
            page_bytes = native_bytes
        else:
            # 1 byte/element storage + the per-page per-head scale.
            page_bytes = (self.num_paged_layers * kv_loc
                          * (page * self.head_dim + 4))
        plan = {
            "page": page, "p_max": p_max, "num_pages": num_pages,
            "kv_heads_loc": kv_loc,
            "kv_dtype": "bf16" if qdtype is None else kv_dtype,
            "page_bytes_per_rank": 2 * page_bytes,      # K and V
            "native_page_bytes_per_rank": 2 * native_bytes,
            "pool_bytes_per_rank": 2 * page_bytes * num_pages,
            "bytes_per_token": 2 * page_bytes / page,
            "capacity_ratio_vs_native": round(
                native_bytes / page_bytes, 4),
            "tokens_per_page": page,
        }
        return plan

    def layer_is_full_attn(self, layer_idx: int) -> bool:
        """Hybrid schedule: layers (interval-1, 2·interval-1, …) are full
        attention, the rest GDN (Qwen3-Next places the softmax layer
        last in each block of `full_attn_interval`)."""
        if not self.is_hybrid:
            return True
        return layer_idx % self.full_attn_interval == (
            self.full_attn_interval - 1)

    @classmethod
    def qwen3_8b(cls) -> "ModelConfig":
        return cls(hidden_size=4096, intermediate_size=12288,
                   num_hidden_layers=36, num_attention_heads=32,
                   num_key_value_heads=8, head_dim=128,
                   model_name="qwen3-8b")

    @classmethod
    def qwen3_32b(cls) -> "ModelConfig":
        return cls(hidden_size=5120, intermediate_size=25600,
                   num_hidden_layers=64, num_attention_heads=64,
                   num_key_value_heads=8, head_dim=128,
                   model_name="qwen3-32b")

    @classmethod
    def qwen3_moe_30b_a3b(cls) -> "ModelConfig":
        """Qwen3-30B-A3B (reference MoE demo, models/qwen_moe.py)."""
        return cls(hidden_size=2048, intermediate_size=6144,
                   num_hidden_layers=48, num_attention_heads=32,
                   num_key_value_heads=4, head_dim=128,
                   num_experts=128, num_experts_per_tok=8,
                   moe_intermediate_size=768,
                   model_name="qwen3-moe-30b-a3b")

    @classmethod
    def tiny_moe(cls, **kw) -> "ModelConfig":
        base = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=2, num_attention_heads=8,
                    num_key_value_heads=8, head_dim=8, num_experts=16,
                    num_experts_per_tok=2, moe_intermediate_size=32,
                    model_name="qwen3-moe-tiny")
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_latent_moe(cls, **kw) -> "ModelConfig":
        """Latent attention over routed experts at a size for the CPU
        mesh: YaRN with an original length of 16, so that a short test
        crosses it; 16 experts, 4 a token, 4 of them held."""
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=4,
                    head_dim=16, q_lora_rank=32, kv_lora_rank=16,
                    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
                    rope_theta=10000.0, rope_factor=8.0,
                    rope_original_max_position=16, rope_beta_fast=4.0,
                    rope_beta_slow=1.0, rope_mscale=1.0,
                    rope_mscale_all_dim=1.0, rope_query_scale_beta=0.1,
                    num_experts=16, num_experts_per_tok=4,
                    moe_intermediate_size=32,
                    shared_expert_intermediate_size=32,
                    first_held_expert=0, num_held_experts=4,
                    max_position_embeddings=128,
                    model_name="latent-moe-tiny")
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_mamba_moe(cls, **kw) -> "ModelConfig":
        """Mamba-2 layers, latent experts behind a sigmoid router and
        one attention layer at a size for the CPU mesh: 16 experts, 4 a
        token, 4 of them held; scan chunks of 8 rows."""
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=6,
                    layer_pattern="MEM*EM", num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-5,
                    mamba_num_heads=8, mamba_head_dim=16, mamba_n_groups=2,
                    ssm_state_size=16, mamba_conv_kernel=4,
                    mamba_chunk_size=8, num_experts=16,
                    num_experts_per_tok=4, moe_scoring="sigmoid",
                    moe_act="relu2", moe_latent_size=32,
                    moe_intermediate_size=48,
                    shared_expert_intermediate_size=96,
                    routed_scaling_factor=5.0, first_held_expert=0,
                    num_held_experts=4, max_position_embeddings=128,
                    model_name="mamba-moe-tiny")
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_window_moe(cls, **kw) -> "ModelConfig":
        """Window and global layers mixed over routed experts at a size
        for the CPU mesh (models/window_moe.py): two periods ``LLLG``,
        a window of 8, one leading dense layer, 8 experts behind a
        sigmoid router, 2 a token, 2 of them held, a shared one."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    num_hidden_layers=8, attn_pattern="LLLGLLLG",
                    sliding_window=8, first_dense_layers=1,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16, rms_norm_eps=1e-5, rope_theta=10000.0,
                    num_experts=8, num_experts_per_tok=2,
                    moe_scoring="sigmoid", moe_intermediate_size=32,
                    shared_expert_intermediate_size=32,
                    routed_scaling_factor=2.5, first_held_expert=0,
                    num_held_experts=2, max_position_embeddings=256,
                    model_name="window-moe-tiny")
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_looped(cls, **kw) -> "ModelConfig":
        """Two four-norm blocks applied three times at a size for the
        CPU mesh (models/looped.py)."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=2,
                    num_key_value_heads=2, head_dim=32, qk_norm=False,
                    rope_theta=10000.0, max_position_embeddings=128,
                    num_passes=3, post_norm=True,
                    model_name="looped-tiny")
        base.update(kw)
        return cls(**base)

    @classmethod
    def _from_nemotron_h(cls, get, req) -> "ModelConfig":
        """``model_type: nemotron_h``: ``hybrid_override_pattern`` gives
        every layer ONE part (models/mamba_moe.py)."""
        pattern = req("hybrid_override_pattern")
        n_layers = req("num_hidden_layers")
        if len(pattern) != n_layers or set(pattern) - set("ME*"):
            raise NotImplementedError(
                f"hybrid_override_pattern {pattern!r}: one of 'M', 'E', "
                f"'*' for each of the {n_layers} layers is served (a "
                "dense '-' layer is not)")
        if (get("n_group") or 1) != 1 or (get("topk_group") or 1) != 1:
            raise NotImplementedError(
                f"n_group={get('n_group')}, topk_group="
                f"{get('topk_group')}: group-limited routing is not "
                "computed; the router chooses among all experts")
        if get("mlp_hidden_act", "relu2") != "relu2" or get(
                "mamba_hidden_act", "silu") != "silu":
            raise NotImplementedError(
                "nemotron_h with squared-ReLU experts and a SiLU "
                "convolution is computed, not mlp_hidden_act="
                f"{get('mlp_hidden_act')!r}, mamba_hidden_act="
                f"{get('mamba_hidden_act')!r}")
        if any(get(k) for k in ("attention_bias", "mlp_bias", "use_bias",
                                "mamba_proj_bias")) or not get(
                                    "use_conv_bias", True):
            raise NotImplementedError(
                "nemotron_h is computed with no bias but the "
                "convolution's")
        d, heads = req("hidden_size"), req("mamba_num_heads")
        if heads * req("mamba_head_dim") != (get("expand") or 2) * d:
            raise ValueError("mamba_num_heads * mamba_head_dim is not "
                             "expand * hidden_size")
        return cls(
            vocab_size=req("vocab_size"), hidden_size=d,
            intermediate_size=get("intermediate_size", 4 * d),
            num_hidden_layers=n_layers, layer_pattern=pattern,
            num_attention_heads=req("num_attention_heads"),
            num_key_value_heads=req("num_key_value_heads"),
            head_dim=req("head_dim"),
            rms_norm_eps=get("norm_eps") or get("layer_norm_epsilon")
            or 1e-5,
            # Carried and unused: this family's attention does not
            # rotate (the Mamba layers carry position).
            rope_theta=get("rope_theta") or 10000.0,
            partial_rotary_factor=get("partial_rotary_factor") or 1.0,
            qk_norm=False,
            max_position_embeddings=get("max_position_embeddings", 40960),
            tie_word_embeddings=get("tie_word_embeddings", False),
            model_name="nemotron_h",
            mamba_num_heads=heads, mamba_head_dim=req("mamba_head_dim"),
            mamba_n_groups=req("n_groups"),
            ssm_state_size=req("ssm_state_size"),
            mamba_conv_kernel=req("conv_kernel"),
            mamba_chunk_size=get("chunk_size") or 128,
            num_experts=req("n_routed_experts"),
            num_experts_per_tok=req("num_experts_per_tok"),
            moe_intermediate_size=req("moe_intermediate_size"),
            moe_latent_size=get("moe_latent_size") or 0,
            shared_expert_intermediate_size=get(
                "moe_shared_expert_intermediate_size") or 0,
            norm_topk_prob=get("norm_topk_prob", True),
            routed_scaling_factor=get("routed_scaling_factor") or 1.0,
            moe_scoring="sigmoid", moe_act="relu2")

    @classmethod
    def _from_exaone_moe(cls, get, req, leave_out=()) -> "ModelConfig":
        """``model_type: exaone_moe``: window and global attention by
        ``layer_types``, leading dense layers, sigmoid-routed experts
        and shared ones (models/window_moe.py)."""
        n_layers = req("num_hidden_layers")
        letters = {"sliding_attention": "L", "full_attention": "G"}
        types = req("layer_types")
        if len(types) != n_layers or set(types) - set(letters):
            raise NotImplementedError(
                f"layer_types {types!r}: 'sliding_attention' or "
                f"'full_attention' for each of the {n_layers} layers is "
                "served")
        pattern = "".join(letters[t] for t in types)
        window = get("sliding_window") or 0
        if "L" in pattern and window < 1:
            raise ValueError("sliding_attention layers need a "
                             "sliding_window of 1 or more")
        windows = get("sliding_windows")
        if windows is not None and list(windows) != [
                window if c == "L" else 0 for c in pattern]:
            raise NotImplementedError(
                f"sliding_windows {windows!r}: one window, "
                f"sliding_window={window}, on every sliding_attention "
                "layer and none on the others is served")
        dense = get("first_k_dense_replace") or 0
        mlp_types = get("mlp_layer_types")
        if mlp_types is not None and list(mlp_types) != (
                ["dense"] * dense + ["sparse"] * (n_layers - dense)):
            raise NotImplementedError(
                f"mlp_layer_types {mlp_types!r}: first_k_dense_replace="
                f"{dense} leading dense layers, then sparse ones, is "
                "served")
        rope = get("rope_parameters") or get("rope_scaling") or {}
        kind = rope.get("rope_type") or rope.get("type") or "default"
        if kind != "default":
            raise NotImplementedError(
                f"rope_type {kind!r}: exaone_moe is computed with plain "
                "rope on its window layers")
        if (get("n_group") or 1) != 1 or (get("topk_group") or 1) != 1:
            raise NotImplementedError(
                f"n_group={get('n_group')}, topk_group="
                f"{get('topk_group')}: group-limited routing is not "
                "computed; the router chooses among all experts")
        if (get("num_nextn_predict_layers") or 0) and (
                "mtp" not in leave_out):
            raise NotImplementedError(
                "num_nextn_predict_layers="
                f"{get('num_nextn_predict_layers')}: the multi-token-"
                "prediction module is not computed; say "
                "from_hf_config(..., leave_out=('mtp',)) to serve the "
                "model without it")
        if get("scoring_func", "sigmoid") != "sigmoid" or get(
                "hidden_act", "silu") != "silu":
            raise NotImplementedError(
                "exaone_moe with a sigmoid router and SiLU-gated "
                f"experts is computed, not scoring_func="
                f"{get('scoring_func')!r}, hidden_act="
                f"{get('hidden_act')!r}")
        heads = req("num_attention_heads")
        d = req("hidden_size")
        ff = req("moe_intermediate_size")
        return cls(
            vocab_size=req("vocab_size"), hidden_size=d,
            intermediate_size=req("intermediate_size"),
            num_hidden_layers=n_layers, attn_pattern=pattern,
            sliding_window=window, first_dense_layers=dense,
            num_attention_heads=heads,
            num_key_value_heads=get("num_key_value_heads", heads),
            head_dim=get("head_dim") or d // heads, qk_norm=True,
            rms_norm_eps=get("rms_norm_eps", 1e-5),
            rope_theta=get("rope_theta") or rope.get("rope_theta",
                                                     1_000_000.0),
            max_position_embeddings=get("max_position_embeddings", 40960),
            tie_word_embeddings=get("tie_word_embeddings", False),
            model_name="exaone_moe",
            num_experts=req("num_experts"),
            num_experts_per_tok=req("num_experts_per_tok"),
            moe_intermediate_size=ff,
            shared_expert_intermediate_size=ff * (
                get("num_shared_experts") or 0),
            norm_topk_prob=get("norm_topk_prob", True),
            routed_scaling_factor=get("routed_scaling_factor") or 1.0,
            moe_scoring="sigmoid")

    @classmethod
    def qwen3_next_80b_a3b(cls) -> "ModelConfig":
        """Qwen3-Next-80B-A3B geometry: 48 layers, 3 GDN : 1 full-attn,
        MoE FFN (512 experts, 10 active + shared omitted)."""
        return cls(hidden_size=2048, intermediate_size=5120,
                   num_hidden_layers=48, num_attention_heads=16,
                   num_key_value_heads=2, head_dim=256,
                   num_experts=512, num_experts_per_tok=10,
                   moe_intermediate_size=512,
                   gdn_num_heads=32, gdn_head_dim_k=128,
                   gdn_head_dim_v=128, full_attn_interval=4,
                   model_name="qwen3-next-80b-a3b")

    @classmethod
    def tiny_next(cls, **kw) -> "ModelConfig":
        """Hybrid GDN/full-attention tiny config for the CPU mesh."""
        base = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=4, num_attention_heads=8,
                    num_key_value_heads=8, head_dim=8,
                    gdn_num_heads=8, gdn_head_dim_k=8, gdn_head_dim_v=8,
                    full_attn_interval=2, model_name="qwen3-next-tiny")
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, *, vocab_size: int = 256, hidden_size: int = 32,
             intermediate_size: int = 64, num_hidden_layers: int = 2,
             num_attention_heads: int = 8, num_key_value_heads: int = 8,
             head_dim: int = 8) -> "ModelConfig":
        """Small enough that every pallas buffer stays under the
        interpret-mode 64 KB/device limit on the CPU test mesh."""
        return cls(vocab_size=vocab_size, hidden_size=hidden_size,
                   intermediate_size=intermediate_size,
                   num_hidden_layers=num_hidden_layers,
                   num_attention_heads=num_attention_heads,
                   num_key_value_heads=num_key_value_heads,
                   head_dim=head_dim, model_name="qwen3-tiny")

    @classmethod
    def from_hf_config(cls, hf_cfg, leave_out=()) -> "ModelConfig":
        """Build from a transformers AutoConfig OR a raw ``config.json``
        dict (reference loads HF checkpoints, ``models/dense.py:150``).
        The single HF→ModelConfig mapper: covers dense, MoE
        (Qwen3-MoE), and hybrid GDN (Qwen3-Next) field sets.
        ``leave_out``: parts of the published model the caller states
        are served WITHOUT (``"mtp"``: a multi-token-prediction
        module), where the family would otherwise refuse the config.
        """
        if isinstance(hf_cfg, dict):
            get = lambda k, d=None: hf_cfg.get(k, d)
        else:
            get = lambda k, d=None: getattr(hf_cfg, k, d)

        def req(k):
            # Core architecture fields stay REQUIRED: silently
            # defaulting them would build a default-shaped model from a
            # malformed or wrong-schema config.json (ADVICE r4).
            v = get(k)
            if v is None:
                raise KeyError(
                    f"HF config missing required field {k!r} — is this "
                    "a supported config.json?")
            return v

        if get("model_type") == "nemotron_h":
            return cls._from_nemotron_h(get, req)
        if get("model_type") == "exaone_moe":
            return cls._from_exaone_moe(get, req, tuple(leave_out))
        d = req("hidden_size")
        heads = req("num_attention_heads")

        # Hybrid layer schedule: real qwen3_next checkpoints serialize
        # an explicit layer_types list; this config expresses the
        # schedule as an interval (softmax layer last in each block),
        # so verify the list IS that pattern rather than silently
        # reinterpreting a custom schedule.
        #
        # Same fail-fast policy for the MoE schedule: every MoE layer
        # is assumed sparse (decoder_sparse_step 1, no dense-only
        # layers). Rejecting a non-default schedule HERE — before
        # load_hf_checkpoint reads tens of GB of shards — beats an
        # opaque KeyError from the per-layer mapper afterwards.
        if get("num_experts", 0):
            if get("decoder_sparse_step", 1) not in (None, 1) or \
                    get("mlp_only_layers"):
                raise NotImplementedError(
                    "only the every-layer MoE schedule is supported "
                    f"(decoder_sparse_step={get('decoder_sparse_step')}"
                    f", mlp_only_layers={get('mlp_only_layers')})")
        interval = get("full_attention_interval", 4) or 4
        layer_types = get("layer_types")
        # A window on some layer: served with full attention everywhere
        # it would be silently wrong past the window. Only the family
        # that computes it reads such a config (exaone_moe, above).
        windowed = [t for t in (layer_types or ())
                    if "sliding" in str(t) or "chunked" in str(t)]
        if windowed or (get("sliding_window") and get(
                "use_sliding_window", layer_types is None
                and get("model_type") in ("mistral", "mixtral"))):
            raise NotImplementedError(
                f"model_type {get('model_type')!r} with a window "
                f"(sliding_window={get('sliding_window')}, "
                f"{len(windowed)} windowed layer_types): this family "
                "computes full attention on every layer; window layers "
                "are served by models.window_moe (exaone_moe)")
        # Only hybrid (GDN) models consult the schedule.
        if layer_types and (get("linear_num_value_heads", 0) or 0):
            fulls = [i for i, t in enumerate(layer_types)
                     if t == "full_attention"]
            if not fulls:
                interval = len(layer_types) + 1  # pure linear attention
            else:
                interval = fulls[0] + 1
                want = [i for i in range(len(layer_types))
                        if i % interval == interval - 1]
                if fulls != want:
                    raise NotImplementedError(
                        "layer_types is not an every-Nth-layer "
                        f"full-attention schedule (got {layer_types})")
        # Rope scaling: YaRN under latent attention is computed
        # (models/latent_moe.py); any other scaling would be served as
        # plain rope, silently wrong past its original length: refuse.
        rope = get("rope_parameters") or get("rope_scaling") or {}
        if not isinstance(rope, dict):
            rope = dict(vars(rope))
        scaling = rope.get("rope_type") or rope.get("type") or "default"
        latent = {}
        if get("kv_lora_rank"):
            if scaling not in ("default", "yarn"):
                raise NotImplementedError(
                    f"rope scaling {scaling!r} under latent attention: "
                    "only 'yarn' is computed")
            if not get("rope_interleave", True):
                raise NotImplementedError(
                    "latent attention ropes interleaved pairs only "
                    "(rope_interleave false)")
            latent = dict(
                q_lora_rank=req("q_lora_rank"),
                kv_lora_rank=req("kv_lora_rank"),
                qk_nope_head_dim=req("qk_nope_head_dim"),
                qk_rope_head_dim=req("qk_rope_head_dim"),
                v_head_dim=req("v_head_dim"),
                routed_scaling_factor=get("routed_scaling_factor", 1.0),
                rope_query_scale_beta=rope.get(
                    "llama_4_scaling_beta", 0.0) or 0.0)
            if scaling == "yarn":
                latent.update(
                    rope_factor=rope["factor"],
                    rope_original_max_position=rope[
                        "original_max_position_embeddings"],
                    rope_beta_fast=rope.get("beta_fast", 32.0),
                    rope_beta_slow=rope.get("beta_slow", 1.0),
                    rope_mscale=rope.get("mscale", 1.0),
                    rope_mscale_all_dim=rope.get("mscale_all_dim", 0.0))
        elif scaling != "default":
            raise NotImplementedError(
                f"rope scaling {scaling!r} is not computed by this "
                "model family (plain rope only)")
        # ``model_type: ouro`` (models/looped.py): the stack applied
        # ``total_ut_steps`` times, four norms a block, an exit gate.
        looped = {}
        if get("model_type") == "ouro":
            if get("use_sliding_window") or get("sliding_window"):
                raise NotImplementedError(
                    "ouro with a sliding window: every pass of every "
                    "layer is served with full attention")
            looped = dict(
                num_passes=req("total_ut_steps"),
                exit_threshold=float(get("early_exit_threshold", 1.0)),
                post_norm=True)
        n_experts = get("num_experts", 0) or get("n_routed_experts", 0) or 0
        shared_ff = get("shared_expert_intermediate_size", 0) or (
            (get("n_shared_experts", 0) or 0)
            * (get("moe_intermediate_size", 0) or 0))
        return cls(
            **latent, **looped,
            vocab_size=req("vocab_size"),
            hidden_size=d,
            intermediate_size=get("intermediate_size", 4 * d),
            num_hidden_layers=req("num_hidden_layers"),
            num_attention_heads=heads,
            num_key_value_heads=get("num_key_value_heads", heads),
            head_dim=get("head_dim") or d // heads,
            # Qwen2-family configs omit the key but hardcode q/k/v
            # biases in the HF implementation — default from the model
            # type so those checkpoints don't silently drop biases.
            attention_bias=bool(get(
                "attention_bias",
                str(get("model_type", "")).startswith("qwen2"))),
            # The per-head q/k RMS norm is a Qwen3-family trait; bias-
            # carrying llama-style checkpoints (Seed-OSS, the whole
            # Qwen2 family incl. qwen2_moe/qwen2_vl) have no
            # q_norm/k_norm weights.
            qk_norm=not (
                str(get("model_type", "")).startswith("qwen2")
                or get("model_type", "qwen3") in (
                    "seed_oss", "llama", "mistral", "ouro")),
            rms_norm_eps=get("rms_norm_eps", 1e-6),
            rope_theta=get("rope_theta") or rope.get(
                "rope_theta", 1_000_000.0),
            max_position_embeddings=get("max_position_embeddings", 40960),
            tie_word_embeddings=get("tie_word_embeddings", False),
            model_name=get("model_type", "qwen3"),
            num_experts=n_experts,
            num_experts_per_tok=get("num_experts_per_tok", 8) or 8,
            moe_intermediate_size=get("moe_intermediate_size", 768) or 768,
            norm_topk_prob=get("norm_topk_prob", True),
            gdn_num_heads=get("linear_num_value_heads", 0) or 0,
            gdn_num_key_heads=get("linear_num_key_heads", 0) or 0,
            gdn_head_dim_k=get("linear_key_head_dim", 128) or 128,
            gdn_head_dim_v=get("linear_value_head_dim", 128) or 128,
            full_attn_interval=interval,
            # qwen3_next checkpoints use the HF GatedDeltaNet cell,
            # gated attention, and partial RoPE; other model types keep
            # the plain-field defaults.
            gdn_conv_kernel=(get("linear_conv_kernel_dim", 4) or 4
                             if get("model_type") == "qwen3_next" else 0),
            attn_gate=get("model_type") == "qwen3_next",
            partial_rotary_factor=(
                get("partial_rotary_factor", 0.25) or 0.25
                if get("model_type") == "qwen3_next" else 1.0),
            shared_expert_intermediate_size=shared_ff,
            norm_zero_centered=get("model_type") == "qwen3_next",
        )
