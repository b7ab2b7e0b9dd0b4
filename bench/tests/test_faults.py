"""A whole run with the timed path broken underneath, the look for a chip
skipped: ``correct`` has to come out false, once for each fault a cell can
have. The program runs in float32 here, so the limit is set the chip's
way from this size's own readings (sound runs read under 1e-5 on the
mean gap, the number the chat cell compares)."""
from __future__ import annotations

from conftest import CHAT, fp32_config, make_root, run_cell

SERVE_LIMITS = {"logit_gap_mean": {"limit": 1e-3}}
E2E_SERVE = [{"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.05,
              "source": "host_clock"}]


def _serve_root(tmp):
    conf = fp32_config()
    return make_root(tmp, "tiny.chat", conf, CHAT, SERVE_LIMITS, E2E_SERVE)


def test_sound_serving_run_is_correct(tmp_path):
    assert run_cell(_serve_root(tmp_path), "tiny.chat")["correct"]


def test_altered_token_is_not_correct(tmp_path, monkeypatch):
    from repro.serving.server import Server

    commit = Server._commit

    def altered(self, req, token, events):
        """Every request's third token is another than the one sampled."""
        return commit(self, req, (token + 128) % 256 if len(req.out_tokens) == 2 else token,
                      events)

    monkeypatch.setattr(Server, "_commit", altered)
    assert not run_cell(_serve_root(tmp_path), "tiny.chat")["correct"]


def test_decode_leaving_the_cache_unchanged_is_not_correct(tmp_path, monkeypatch):
    """A decode step that returns its state (the KV pages) unchanged."""
    from repro.serving.engine import EngineCore

    dispatch = EngineCore.dispatch_decode

    def stale(self, **kw):
        pools = self.cache.pools
        dispatch(self, **kw)
        self.cache.pools = pools

    monkeypatch.setattr(EngineCore, "dispatch_decode", stale)
    assert not run_cell(_serve_root(tmp_path), "tiny.chat")["correct"]
