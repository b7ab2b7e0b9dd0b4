"""The harness's contract on the CPU: no TPU means no result; the peaks
table refuses an unknown device; a metric is added by adding its file; a
configuration brings its reference and its program fields as files."""
from __future__ import annotations

import pytest

import common
import run
from conftest import CHAT, fp32_config, make_root, run_cell, tiny_config


def test_unknown_device_raises():
    with pytest.raises(common.BenchError):
        common.peaks("TPU v99")
    assert common.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    rc = run.main(["--workload", "granite-3-8b.chat", "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_a_metric_is_added_by_adding_its_file(tmp_path):
    reader = ('"""Requests due in the window, per second."""\n\n\n'
              'def read(rec):\n    return rec["attempted"] / rec["window_s"]\n')
    conf = tiny_config("granite-3-8b-serve", backend="xla")
    e2e = [{"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.05,
            "source": "host_clock"},
           {"name": "due_per_s", "unit": "1/s", "better": "higher", "bound": 0.05,
            "source": "host_clock"}]
    root = make_root(tmp_path, "tiny.chat", conf, CHAT, {"logit_gap": {"limit": 10.0}}, e2e,
                     extra_metrics={"due_per_s": reader})
    result = run_cell(root, "tiny.chat")
    assert set(result["metrics"]) == {"itl_p95_ms", "due_per_s"}
    assert result["metrics"]["due_per_s"]["value"] > 0
    assert list(result)[-1] == "checks"


# -- a configuration of another architecture, as files only -------------------

GEGLU = '''"""Plain float32 reference of a dense decoder whose feed-forward gate
takes GELU (tanh form) in place of SiLU; the rest is ``decoder``'s."""
import functools

import jax
import jax.numpy as jnp

from reference import decoder
from reference.decoder import Dims  # noqa: F401

CALLS = []


@functools.partial(jax.jit, static_argnums=0, static_argnames="quant")
def _layer(dims, w, x, quant=None):
    x = decoder.attn_block(dims, w, x, quant)
    h = decoder.rms_norm(x, w["norm2"], dims.eps)
    gate = jax.nn.gelu(decoder.mm(h, w["gate"], quant), approximate=True)
    return x + decoder.mm(gate * decoder.mm(h, w["up"], quant), w["down"], quant)


def logits_at(dims, key, tokens, rows, cols, quant=None):
    CALLS.append(len(rows))
    table = decoder.embed_table(dims, key)
    x = table[jnp.asarray(tokens)]
    for i in range(dims.layers):
        x = _layer(dims, decoder.layer_weights(dims, key, i), x, quant=quant)
    x = x[jnp.asarray(rows), jnp.asarray(cols)]
    final = jnp.ones((dims.d_model,), jnp.float32)
    return decoder.head(dims, table, final, x[None], quant=quant)[0]
'''
FP32_LIMITS = {"logit_gap_mean": {"limit": 1e-3}}  # as test_faults.py's, from its readings
E2E = [{"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.05,
        "source": "host_clock"}]


def _geglu_config(reference="geglu"):
    conf = fp32_config(model={"act": "geglu"})
    conf["hidden_act"] = "gelu_pytorch_tanh"
    if reference:
        conf["reference"] = reference
    return conf


def test_a_configuration_brings_its_own_reference(tmp_path):
    """A configuration of an architecture the repository's references do
    not cover (GeGLU feed-forward, a program field set by ``program.model``)
    runs end to end in a checkout that differs from the repository's only
    by added files, and is judged by its own reference module."""
    conf = _geglu_config()
    root = make_root(tmp_path, "tiny.chat", conf, CHAT, FP32_LIMITS, E2E,
                     extra_references={"geglu": GEGLU})
    result = run_cell(root, "tiny.chat")
    assert result["correct"], result["checks"]
    assert common.reference(conf, root).CALLS


def test_the_default_reference_judges_the_wrong_architecture(tmp_path):
    """The same program judged by ``decoder`` (the file names no reference)
    is not correct: the reference named by the file is the one that ran."""
    root = make_root(tmp_path, "tiny.chat", _geglu_config(reference=None), CHAT, FP32_LIMITS,
                     E2E)
    assert not run_cell(root, "tiny.chat")["correct"]


def test_naming_decoder_reads_as_no_name(tmp_path):
    """``"reference": "decoder"`` gives the readings of a file with no key."""
    checks = []
    for sub, reference in (("none", None), ("named", "decoder")):
        conf = fp32_config()
        if reference:
            conf["reference"] = reference
        root = make_root(tmp_path / sub, "tiny.chat", conf, CHAT, FP32_LIMITS, E2E)
        checks.append(run_cell(root, "tiny.chat")["checks"])
    assert checks[0] == checks[1]


@pytest.mark.parametrize("change", [{"reference": "no_such_module"},
                                    {"reference": "../decoder"},
                                    {"program": {"model": {"no_such_field": 1}}}],
                         ids=["unknown-reference", "reference-path", "unknown-field"])
def test_unknown_names_are_refused_before_setup(tmp_path, monkeypatch, change):
    import serve

    def setup(self, seconds):
        raise AssertionError("set-up ran")

    monkeypatch.setattr(serve.ServeCell, "setup", setup)
    conf = fp32_config()
    for key, value in change.items():
        if isinstance(value, dict):
            conf[key].update(value)
        else:
            conf[key] = value
    root = make_root(tmp_path, "tiny.chat", conf, CHAT, FP32_LIMITS, E2E)
    with pytest.raises(common.BenchError):
        run_cell(root, "tiny.chat")


MOE = {"num_local_experts": 16, "num_experts_per_tok": 8, "intermediate_size": 32}


@pytest.mark.parametrize("model, correct", [
    ({"n_experts": 16, "top_k": 8, "block_pattern": ["attn"], "family": "moe"}, True),
    ({"top_k": 4}, False),
], ids=["as-published", "other-than-published"])
def test_program_model_sets_fields(tmp_path, model, correct):
    """``program.model`` reaches the program's ``ModelConfig`` (a JSON list
    as a tuple); a program run otherwise than the file publishes fails
    ``correct``, the guard on what the field may set."""
    from program import model_config

    conf = fp32_config(arch="granite-moe-1b-a400m", model=model)
    conf.update(MOE)
    cfg = model_config(conf)
    for field, value in model.items():
        assert getattr(cfg, field) == (tuple(value) if isinstance(value, list) else value)
    root = make_root(tmp_path, "tiny.chat", conf, CHAT, FP32_LIMITS, E2E)
    assert run_cell(root, "tiny.chat")["correct"] is correct


def test_head_dim_from_the_file(tmp_path):
    """A file stating ``head_dim`` other than ``hidden_size /
    num_attention_heads``: program and reference both run it, and agree."""
    from program import model_config
    from reference import decoder

    conf = fp32_config()
    conf["head_dim"] = 32
    assert model_config(conf).head_dim == decoder.Dims.from_config(conf).head_dim == 32
    root = make_root(tmp_path, "tiny.chat", conf, CHAT, FP32_LIMITS, E2E)
    result = run_cell(root, "tiny.chat")
    assert result["correct"], result["checks"]
