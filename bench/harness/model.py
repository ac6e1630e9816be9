"""From a configuration file to the program's model config and weights.

The file (``bench/configs/<config>.json``) states the model as it is run,
with the keys of its published ``config.json`` where there is one, plus
``norm_type``, ``qk_norm`` and ``mlp_gated`` where the published file leaves
them to the architecture's code.  When it names a ``repro_config``, the
program's own config of that name is run, and every size must agree with
the file; otherwise the program's config is built from the file.
"""
from __future__ import annotations

ACT_TO_MLP = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}


def program_config(c: dict):
    from repro.models.config import BlockSpec, ModelConfig
    if not c["mlp_gated"] or not c["tie_word_embeddings"]:
        raise ValueError("the program runs gated MLPs with a tied head only")
    mine = ModelConfig(
        name=c["name"], arch_type="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        pattern=(BlockSpec(kind="attn", mlp=ACT_TO_MLP[c["hidden_act"]]),),
        qk_norm=c["qk_norm"], qkv_bias=c["attention_bias"],
        norm="layernorm" if c["norm_type"] == "layer_norm" else "rmsnorm",
        rope_theta=float(c["rope_theta"]), tie_embeddings=True,
        dtype=c["torch_dtype"])
    name = c.get("repro_config")
    if not name:
        return mine
    from repro.configs import get_config
    theirs = get_config(name)
    fields = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab_size", "pattern", "qk_norm", "qkv_bias", "norm",
              "rope_theta", "tie_embeddings", "dtype", "pos_emb", "post_norm",
              "attn_logit_softcap", "final_logit_softcap", "kv_dtype")
    diff = {f: (getattr(mine, f), getattr(theirs, f)) for f in fields
            if getattr(mine, f) != getattr(theirs, f)}
    if mine.resolved_head_dim != theirs.resolved_head_dim:
        diff["head_dim"] = (mine.resolved_head_dim, theirs.resolved_head_dim)
    if diff:
        raise ValueError(f"{name}: the file and the program's config differ "
                         f"(file, program): {diff}")
    return theirs


def program_params(cfg):
    """(shapes, logical axes) of the program's parameters for ``cfg``."""
    import jax

    from repro.models import transformer as T
    axes = {}

    def init(k):
        params, axes["tree"] = T.init_params(cfg, k)
        return params

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    return shapes, axes["tree"]


def shardings_on(cfg, mesh):
    """The program's tensor-parallel placement of every weight on ``mesh``
    (as its ``init_params_on_mesh`` builds them)."""
    from repro.sharding.rules import default_rules, shape_aware_sharding_tree
    shapes, axes = program_params(cfg)
    return shape_aware_sharding_tree(shapes, axes, mesh,
                                     default_rules("pod" in mesh.axis_names))
