"""The harness's contract on the CPU: no TPU means no result; the peaks
table refuses an unknown device; a metric is added by adding its file."""
from __future__ import annotations

import pytest

import common
import run
from conftest import CHAT, make_root, run_cell, tiny_config


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
