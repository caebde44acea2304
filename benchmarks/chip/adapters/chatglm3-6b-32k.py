"""How the program is told to run ChatGLM3-6B-32K: its ArchConfig, built
from the widths that ``reference/chatglm3-6b-32k.py`` reads from the
configuration file, and where each of the reference's leaves sits in the
program's parameter tree."""

#: reference leaves at the top of the program's tree
TOP_LEAVES = ("embed", "lm_head", "final_norm")
#: reference layer leaf → path inside one block of the program's tree
LAYER_PATHS = {
    "attn_norm": ("norm1",), "ffn_norm": ("norm2",),
    "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
    "wo": ("mixer", "wo"),
    "w1": ("ffn", "w1"), "w3": ("ffn", "w3"), "w2": ("ffn", "w2"),
}


def arch_config(cfg: dict, n: dict):
    """Every layer is grouped-query attention with rotary positions on
    the first ``rot`` dims of each head, then a dense SwiGLU."""
    from repro.models.config import ArchConfig, LayerSpec

    return ArchConfig(
        name=cfg["name"], source=cfg["source"], family="dense",
        d_model=n["d"], n_heads=n["heads"], n_kv_heads=n["kv_heads"],
        head_dim=n["head_dim"], d_ff=n["ffn"], vocab=n["vocab"],
        pattern=(LayerSpec(mixer="attn", ffn="dense",
                           rope_fraction=n["rot"] / n["head_dim"]),),
        repeats=n["layers"], rope_theta=n["theta"])
