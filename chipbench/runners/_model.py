"""From a configuration file (the published ``config.json`` keys, as
run) to the program's ``TransformerConfig``, and to the sizes the counts
need."""

from __future__ import annotations


def sizes(config: dict) -> dict:
    return {
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "d_ff": config["intermediate_size"],
        "n_layers": config["num_hidden_layers"],
        "vocab": config["vocab_size"],
    }


def transformer_config(config: dict):
    import jax.numpy as jnp

    from mpistragglers_jl_tpu.models.transformer import TransformerConfig

    program = config["program"]
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        attn=program.get("attn", "ulysses"),
        attn_impl=program.get("attn_impl", "flash"),
        attn_window=config["sliding_window"],
        remat=bool(program.get("remat", False)),
        dtype=jnp.dtype(config["torch_dtype"]),
    )


def param_shapes(config: dict):
    import jax.numpy as jnp

    from chipbench import weights

    return weights.transformer_shapes(
        dtype=jnp.dtype(config["torch_dtype"]), **sizes(config)
    )


def reference_module(run):
    """The configuration's plain reference,
    ``chipbench/references/<reference>.py`` of the run's checkout."""
    from chipbench.run import load_from

    return load_from(run.root, "references", run.config["reference"])
