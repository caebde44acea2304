"""How the program is told to run the tiny hybrid test decoder
(``tiny_hybrid_reference.py``): two pattern positions, attention with a
dense SwiGLU and attention with a MoE (its kernels in Pallas interpret
mode), repeated, with LayerNorm."""

#: top-level reference leaf → path in the program's tree
TOP_PATHS = {"embed": ("embed",), "lm_head": ("lm_head",),
             "final_norm_w": ("final_norm", "w"),
             "final_norm_b": ("final_norm", "b")}
#: reference layer leaf → path inside the layer's subtree, for both kinds
LAYER_PATHS = {
    "attn_norm_w": ("norm1", "w"), "attn_norm_b": ("norm1", "b"),
    "ffn_norm_w": ("norm2", "w"), "ffn_norm_b": ("norm2", "b"),
    "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
    "wo": ("mixer", "wo"),
    "router": ("ffn", "router"), "w1": ("ffn", "w1"), "w3": ("ffn", "w3"),
    "w2": ("ffn", "w2"),
}


def arch_config(cfg: dict, n: dict):
    from repro.models.config import ArchConfig, LayerSpec

    return ArchConfig(
        name=cfg["name"], source=cfg["source"], family="moe",
        d_model=n["d"], n_heads=n["heads"], n_kv_heads=n["kv_heads"],
        head_dim=n["head_dim"], d_ff=n["ffn"], vocab=n["vocab"],
        pattern=(LayerSpec(mixer="attn", ffn="dense"),
                 LayerSpec(mixer="attn", ffn="moe")),
        repeats=n["layers"] // 2, moe_experts=n["experts"],
        moe_top_k=n["top_k"], moe_d_ff=n["moe_ffn"],
        moe_capacity_factor=n["capacity_factor"], rope_theta=n["theta"],
        norm="ln", moe_impl="interpret")
