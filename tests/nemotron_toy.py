"""The Nemotron-H family at a toy size, for the CPU tests that share it
(test_nemotron_h.py, test_mamba2.py, test_moe.py): hidden 64, Mamba-2 16
heads x 8 in 4 groups with a state of 16 and chunks of 8, 8 query / 2 K/V
heads of 16, 16 latent experts top-6 of which this share holds 4 (latent 32,
width 48, shared 96), the first 11 layers of the published pattern (one
whole period: 5 M, 5 E, 1 *).  Every key of the published ``config.json`` is
here under its published name."""
import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 512
PUBLISHED_KEYS = dict(
    attention_bias=False, chunk_size=8, conv_kernel=4, expand=2, head_dim=16,
    hidden_size=64,
    hybrid_override_pattern=(
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
    intermediate_size=48, layer_norm_epsilon=1e-5, mamba_head_dim=8,
    mamba_hidden_act="silu", mamba_num_heads=16, mamba_proj_bias=False,
    max_position_embeddings=256, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, moe_shared_expert_overlap=False,
    mtp_hybrid_override_pattern="*E", n_group=1, n_groups=4, n_routed_experts=4,
    n_shared_experts=1, norm_eps=1e-5, norm_topk_prob=True,
    num_attention_heads=8, num_experts_per_tok=6, num_hidden_layers=11,
    num_key_value_heads=2, num_logits_to_keep=1, num_nextn_predict_layers=1,
    partial_rotary_factor=1, rescale_prenorm_residual=True,
    residual_in_fp32=False, rope_theta=10000, routed_scaling_factor=5,
    sliding_window=None, ssm_state_size=16, tie_word_embeddings=False,
    time_step_floor=1e-4, time_step_max=0.1, time_step_min=0.001, topk_group=1,
    use_bias=False, use_conv_bias=True, use_mamba_kernels=True, vocab_size=VOCAB,
)
# a configuration file's shape: the published keys (n_routed_experts counts
# the experts HELD), what the reference needs, and what is run
CONFIG = dict(
    PUBLISHED_KEYS, reference_pad_to=32, reference_query_block=32,
    assumed={"router_logit_std": 1.0, "correction_bias_std": 0.05},
    serve={"model": {"n_routed_experts": 16, "experts_held": [4, 4]}},
)
MODEL_KEYS = dict(
    {k: v for k, v in PUBLISHED_KEYS.items() if k != "vocab_size"},
    **CONFIG["serve"]["model"])


def load_reference():
    """benchmark/reference/nemotron_h.py as a module of its own: it imports
    nothing of the program."""
    path = os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("reference_nemotron_h", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
