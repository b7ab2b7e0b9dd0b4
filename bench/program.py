"""The program under test, built from a configuration file: the published
widths come from the file's top level, the engine settings and any other
``ModelConfig`` field from its ``program`` group."""
from __future__ import annotations

import dataclasses

from common import BenchError


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file.

    The architecture's registered config (``program.arch``) takes the
    published keys, ``head_dim`` where the file states one, then
    ``program.model``: ``ModelConfig`` fields the published keys do not
    reach (a layer pattern, state widths, the experts held), a JSON list
    becoming a tuple. The reference reads the same published keys, so a
    program run other than as published fails ``correct``."""
    from repro.configs import get_config

    p = conf["program"]
    base = get_config(p["arch"])
    extra = p.get("model", {})
    unknown = sorted(set(extra) - {f.name for f in dataclasses.fields(base)})
    if unknown:
        raise BenchError(f"configuration {conf.get('name')!r}: program.model names "
                         f"{unknown}, which are not fields of ModelConfig")
    fields = dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim", conf["hidden_size"] // conf["num_attention_heads"]),
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=conf["rope_theta"],
        n_experts=conf.get("num_local_experts", 0),
        top_k=conf.get("num_experts_per_tok", 0),
        policy=p["policy"], backend=p["backend"],
        kv_cache_dtype=p.get("kv_cache_dtype", "bf16"),
    )
    fields.update((k, tuple(v) if isinstance(v, list) else v) for k, v in extra.items())
    return dataclasses.replace(base, **fields)
