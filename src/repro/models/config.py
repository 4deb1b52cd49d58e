"""Architecture configuration dataclass shared by all model families."""
from __future__ import annotations

import dataclasses
import math

# The published (Hugging Face) config's keys a configuration may state
# beside the program's own, in the order of ModelConfig's InitVars.  A
# benchmark configuration file carries its source's numbers under these
# names; each is checked against what the program runs and none is stored.
PUBLISHED = ("attention_bias", "hidden_act", "hidden_size",
             "intermediate_size", "layer_types", "mlp_layer_types",
             "max_position_embeddings", "max_window_layers", "model_type",
             "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
             "num_experts", "num_experts_per_tok", "num_hidden_layers",
             "num_key_value_heads", "rms_norm_eps", "rope_parameters",
             "sliding_window", "tie_word_embeddings", "vocab_size",
             "use_sliding_window")
# published key -> the field that runs it
_FIELD_OF = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "moe_intermediate_size": "d_ff", "vocab_size": "vocab",
             "num_hidden_layers": "n_layers", "num_experts": "n_experts",
             "num_experts_per_tok": "top_k", "sliding_window": "window",
             "rms_norm_eps": "norm_eps",
             "tie_word_embeddings": "tie_embeddings"}
# what the program always runs, having no field for it
_FIXED = {"attention_bias": False, "norm_topk_prob": True}
_KIND_OF = {"full_attention": "global", "sliding_attention": "local"}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"            # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 512
    vocab: int = 1024
    head_dim: int | None = None

    # attention
    rope_theta: float = 10_000.0
    # YaRN on the global ("full attention") layers; factor 0 = plain RoPE
    # everywhere (``layers.rope_tables`` has the equations)
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    window: int | None = None                # sliding-window size (if any)
    local_global_period: int | None = None   # gemma2: 1 global per P layers
    n_global_layers: int = 0                 # hymba: this many global layers
    logit_softcap: float | None = None
    attn_softcap: float | None = None
    qk_norm: bool = False

    # mlp
    mlp: str = "silu_gated"  # silu_gated | gelu_gated | relu2 | gelu

    # moe
    n_experts: int = 0                       # experts this device holds
    top_k: int = 0
    # the router's outputs, every expert of the layer (0: n_experts); a
    # device holds ids [expert_offset, expert_offset + n_experts) of them
    router_experts: int = 0
    expert_offset: int = 0
    moe_dense_residual: bool = False         # arctic: dense FFN in parallel
    capacity_factor: float = 1.25

    # ssm (mamba2 / hybrid)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_kernel: int = 4

    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    post_norm: bool = False                  # gemma2: post-block RMSNorms
    dtype: str = "float32"

    # execution knobs (scale/perf, not architecture)
    scan_layers: bool = True                 # lax.scan over stacked layers
    q_chunk: int = 1024                      # flash-attention block sizes
    kv_chunk: int = 1024
    loss_chunk: int = 512                    # T-chunk for the xent scan
    cache_dtype: str = "bfloat16"
    # modality frontend stub: if True the model also accepts precomputed
    # frame/patch embeddings instead of token ids (audio / vlm families)
    embedding_inputs: bool = False

    # the published keys (PUBLISHED, same order); None: not stated
    attention_bias: dataclasses.InitVar[bool | None] = None
    hidden_act: dataclasses.InitVar[str | None] = None
    hidden_size: dataclasses.InitVar[int | None] = None
    intermediate_size: dataclasses.InitVar[int | None] = None
    layer_types: dataclasses.InitVar[list | None] = None
    mlp_layer_types: dataclasses.InitVar[list | None] = None
    max_position_embeddings: dataclasses.InitVar[int | None] = None
    max_window_layers: dataclasses.InitVar[int | None] = None
    model_type: dataclasses.InitVar[str | None] = None
    moe_intermediate_size: dataclasses.InitVar[int | None] = None
    norm_topk_prob: dataclasses.InitVar[bool | None] = None
    num_attention_heads: dataclasses.InitVar[int | None] = None
    num_experts: dataclasses.InitVar[int | None] = None
    num_experts_per_tok: dataclasses.InitVar[int | None] = None
    num_hidden_layers: dataclasses.InitVar[int | None] = None
    num_key_value_heads: dataclasses.InitVar[int | None] = None
    rms_norm_eps: dataclasses.InitVar[float | None] = None
    rope_parameters: dataclasses.InitVar[dict | None] = None
    sliding_window: dataclasses.InitVar[int | None] = None
    tie_word_embeddings: dataclasses.InitVar[bool | None] = None
    vocab_size: dataclasses.InitVar[int | None] = None
    use_sliding_window: dataclasses.InitVar[bool | None] = None

    def __post_init__(self, *published):
        if self.head_dim is None and self.n_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not 0 <= self.expert_offset <= self.n_routed - self.n_experts:
            raise ValueError(
                f"held experts [{self.expert_offset}, "
                f"{self.expert_offset + self.n_experts}) are not among the "
                f"router's {self.n_routed}")
        bad = _published_mismatches(self, {
            k: v for k, v in zip(PUBLISHED, published) if v is not None})
        if bad:
            raise ValueError(f"{self.name}: the published config says "
                             f"what this config does not run: "
                             f"{'; '.join(bad)}")

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    @property
    def n_routed(self) -> int:
        """Experts the router chooses among."""
        return self.router_experts or self.n_experts

    @property
    def vocab_padded(self) -> int:
        return ((self.vocab + 15) // 16) * 16  # TP-divisible vocab

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        if self.ssm_heads:
            return self.ssm_heads
        return max(1, self.d_inner // self.ssm_head_dim)

    @property
    def sub_quadratic(self) -> bool:
        """True if decode cost is sub-quadratic in context (SSM/hybrid-SWA)."""
        return self.family in ("ssm", "hybrid")

    def layer_kind(self, i: int) -> str:
        """Attention flavour for layer i: 'global' | 'local'."""
        if self.family == "hybrid":
            # hymba: few global layers (first / middle / last), rest SWA
            if self.n_global_layers:
                globals_at = {0, self.n_layers // 2, self.n_layers - 1}
                return "global" if i in globals_at else "local"
            return "global"
        if self.local_global_period:
            return "global" if (i % self.local_global_period ==
                                self.local_global_period - 1) else "local"
        return "global"

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def _published_mismatches(cfg: ModelConfig, given: dict) -> list[str]:
    """Where the published keys ``given`` disagree with ``cfg``.

    ``layer_types`` may list more layers than ``cfg`` runs (a configuration
    cut in depth keeps the first ``n_layers``).  ``intermediate_size`` is
    the dense MLP's width, which a model whose every layer is sparse never
    runs; ``max_position_embeddings``, ``max_window_layers`` (read only
    without ``layer_types``) and ``model_type`` describe the model."""
    bad = []

    def want(key, stated, runs):
        if stated != runs:
            bad.append(f"{key}={stated!r}, runs {runs!r}")

    for key, field in _FIELD_OF.items():
        if key in given:
            want(key, given[key], getattr(cfg, field))
    for key, runs in _FIXED.items():
        if key in given:
            want(key, given[key], runs)
    if "hidden_act" in given:
        want("hidden_act", f"{given['hidden_act']}_gated", cfg.mlp)
    if "intermediate_size" in given and not cfg.n_experts:
        want("intermediate_size", given["intermediate_size"], cfg.d_ff)
    if "use_sliding_window" in given:
        want("use_sliding_window", given["use_sliding_window"],
             cfg.window is not None)
    for i, t in enumerate(given.get("layer_types", [])[:cfg.n_layers]):
        want(f"layer_types[{i}]", _KIND_OF.get(t, t), cfg.layer_kind(i))
    for i, t in enumerate(given.get("mlp_layer_types", [])[:cfg.n_layers]):
        want(f"mlp_layer_types[{i}]", t,
             "sparse" if cfg.n_experts else "dense")
    rope = given.get("rope_parameters", {})
    if "rope_type" in rope:           # one group for every layer
        rope = dict.fromkeys(_KIND_OF, rope)
    for kind, rp in rope.items():
        at = f"rope_parameters.{kind}"
        want(f"{at}.rope_theta", rp.get("rope_theta"), cfg.rope_theta)
        if kind == "full_attention" and rp.get("rope_type") == "yarn":
            f = rp.get("factor")
            want(f"{at}.factor", f, cfg.yarn_factor)
            want(f"{at}.original_max_position_embeddings",
                 rp.get("original_max_position_embeddings"),
                 cfg.yarn_original_max_pos)
            want(f"{at}.beta_fast", rp.get("beta_fast", 32),
                 cfg.yarn_beta_fast)
            want(f"{at}.beta_slow", rp.get("beta_slow", 1),
                 cfg.yarn_beta_slow)
            att = rp.get("attention_factor")
            if att is None and f:
                att = 0.1 * math.log(f) + 1    # transformers' default
            want(f"{at}.attention_factor", att, cfg.yarn_attention_factor)
        else:
            want(f"{at}.rope_type", rp.get("rope_type"), "default")
            if kind == "full_attention":
                want(f"{at}.factor", 0.0, cfg.yarn_factor)
    return bad
