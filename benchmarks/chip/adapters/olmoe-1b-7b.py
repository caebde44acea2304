"""How the program is told to run OLMoE-1B-7B: its ArchConfig, built from
the widths that ``reference/olmoe-1b-7b.py`` reads from the configuration
file, and where each of the reference's leaves sits in the program's
parameter tree."""

#: reference leaves at the top of the program's tree
TOP_LEAVES = ("embed", "lm_head", "final_norm")
#: reference layer leaf → path inside one block of the program's tree
LAYER_PATHS = {
    "attn_norm": ("norm1",), "ffn_norm": ("norm2",),
    "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
    "wo": ("mixer", "wo"), "q_norm": ("mixer", "q_norm"),
    "k_norm": ("mixer", "k_norm"),
    "router": ("ffn", "router"), "w1": ("ffn", "w1"), "w3": ("ffn", "w3"),
    "w2": ("ffn", "w2"),
}


def arch_config(cfg: dict, n: dict):
    """Every layer is attention with per-head qk-norm, then a top-k MoE."""
    from repro.models.config import ArchConfig, LayerSpec

    return ArchConfig(
        name=cfg["name"], source=cfg["source"], family="moe",
        d_model=n["d"], n_heads=n["heads"], n_kv_heads=n["kv_heads"],
        head_dim=n["head_dim"], d_ff=n["ffn"], vocab=n["vocab"],
        pattern=(LayerSpec(mixer="attn", ffn="moe", qk_norm=True),),
        repeats=n["layers"], moe_experts=n["experts"], moe_top_k=n["top_k"],
        moe_d_ff=n["ffn"], moe_capacity_factor=n["capacity_factor"],
        rope_theta=n["theta"])
