"""The program under test, built from a configuration file: every width
comes from the file, the engine settings from its ``program`` group."""
from __future__ import annotations

import dataclasses


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs import get_config

    p = conf["program"]
    return dataclasses.replace(
        get_config(p["arch"]),
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=conf["rope_theta"],
        n_experts=conf.get("num_local_experts", 0),
        top_k=conf.get("num_experts_per_tok", 0),
        policy=p["policy"], backend=p["backend"],
        kv_cache_dtype=p.get("kv_cache_dtype", "bf16"),
    )
