"""Static-analysis subsystem tests (repro.analysis).

Three layers: the AST lint rules (every rule has a fires/clean fixture
pair, plus one regression fixture per historical bug the catalog was
distilled from), the trace-time serving-step contracts (run for real
against one arch per decoder family), and the tuning-table tile validator
(clean on the shipped tables, loud on fabricated bad ones).
"""
import textwrap
import types

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import cli, contracts, rules, tiles
from repro.analysis.rules import lint_source
from repro.kernels import tuning


def _src(code: str) -> str:
    return textwrap.dedent(code).lstrip("\n")


def _rules_of(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# lint rules: fires / clean pair per rule
# ---------------------------------------------------------------------------


class TestRPR101MutableDefault:
    def test_fires(self):
        fs = lint_source(_src("""
            def f(x, acc=[]):
                return acc
        """), "m.py")
        assert _rules_of(fs) == ["RPR101"]

    def test_fires_on_constructor(self):
        fs = lint_source(_src("""
            def f(x, acc=dict()):
                return acc
        """), "m.py")
        assert _rules_of(fs) == ["RPR101"]

    def test_clean_none_sentinel(self):
        fs = lint_source(_src("""
            def f(x, acc=None):
                return [] if acc is None else acc
        """), "m.py")
        assert fs == []


class TestRPR102SharedConfig:
    def test_fires_on_default_arg(self):
        fs = lint_source(_src("""
            def serve(cfg=ServerConfig()):
                return cfg
        """), "serving/server.py")
        assert _rules_of(fs) == ["RPR102"]

    def test_fires_on_module_level(self):
        fs = lint_source(_src("""
            DEFAULT = ServerConfig(num_slots=4)
        """), "serving/server.py")
        assert _rules_of(fs) == ["RPR102"]

    def test_clean_none_sentinel(self):
        fs = lint_source(_src("""
            def serve(cfg=None):
                cfg = cfg or ServerConfig()
                return cfg
        """), "serving/server.py")
        assert fs == []

    def test_configs_zoo_registry_exempt(self):
        # The zoo registry pattern: frozen ModelConfig at module scope in
        # configs/ is by design, not the PR 5 hazard.
        fs = lint_source(_src("""
            CONFIG = ModelConfig(d_model=4096)
        """), "src/repro/configs/some_arch.py")
        assert fs == []

    def test_default_arg_still_fires_in_configs(self):
        fs = lint_source(_src("""
            def make(cfg=ModelConfig()):
                return cfg
        """), "src/repro/configs/some_arch.py")
        assert _rules_of(fs) == ["RPR102"]


class TestRPR103ModuleState:
    def test_fires_on_global_stmt(self):
        fs = lint_source(_src("""
            _next = 0
            def new_rid():
                global _next
                _next += 1
                return _next
        """), "src/repro/serving/api.py")
        assert "RPR103" in _rules_of(fs)

    def test_fires_on_module_mutable(self):
        fs = lint_source(_src("""
            _REGISTRY = {}
        """), "src/repro/serving/api.py")
        assert _rules_of(fs) == ["RPR103"]

    def test_clean_outside_serving(self):
        fs = lint_source(_src("""
            _REGISTRY = {}
            def reg():
                global _REGISTRY
        """), "src/repro/kernels/x.py")
        assert fs == []

    def test_clean_immutable_module_constants(self):
        fs = lint_source(_src("""
            QUEUED = "queued"
            P_BUCKETS = (1, 2, 4, 8)
            __all__ = ["QUEUED"]
        """), "src/repro/serving/api.py")
        assert fs == []


class TestRPR104BareAssert:
    def test_fires(self):
        fs = lint_source(_src("""
            def f(x):
                assert x > 0
        """), "src/repro/kernels/x.py")
        assert _rules_of(fs) == ["RPR104"]

    def test_clean_raise(self):
        fs = lint_source(_src("""
            def f(x):
                if x <= 0:
                    raise ValueError(x)
        """), "src/repro/kernels/x.py")
        assert fs == []


class TestRPR105MirrorAliasing:
    def test_fires(self):
        fs = lint_source(_src("""
            def dispatch(self):
                table = jnp.asarray(self.cache.page_table)
                return table
        """), "src/repro/serving/server.py")
        assert _rules_of(fs) == ["RPR105"]

    def test_fires_on_seq_lens(self):
        fs = lint_source(_src("""
            def dispatch(store):
                return jnp.asarray(store.seq_lens)
        """), "src/repro/serving/spec/drafter.py")
        assert _rules_of(fs) == ["RPR105"]

    def test_clean_with_copy(self):
        fs = lint_source(_src("""
            def dispatch(self):
                return jnp.asarray(self.cache.page_table.copy())
        """), "src/repro/serving/server.py")
        assert fs == []

    def test_clean_outside_serving(self):
        fs = lint_source(_src("""
            def snap(store):
                return jnp.asarray(store.page_table)
        """), "src/repro/roofline/sim.py")
        assert fs == []

    def test_clean_other_attribute(self):
        fs = lint_source(_src("""
            def dispatch(self):
                return jnp.asarray(self.tokens)
        """), "src/repro/serving/server.py")
        assert fs == []


class TestRPR106HotPathSync:
    def test_fires_in_registered_hot_path(self):
        fs = lint_source(_src("""
            class EngineCore:
                def dispatch_decode(self, x):
                    n = int(x.sum())
                    jax.block_until_ready(x)
                    return n
        """), "src/repro/serving/engine.py")
        assert _rules_of(fs) == ["RPR106", "RPR106"]

    def test_fires_in_nested_closure(self):
        fs = lint_source(_src("""
            def dispatch_prefill(self, x):
                def inner():
                    return x.item()
                return inner
        """), "src/repro/serving/engine.py")
        assert _rules_of(fs) == ["RPR106"]

    def test_clean_in_unregistered_function(self):
        fs = lint_source(_src("""
            class EngineCore:
                def harvest_one(self, x):
                    jax.block_until_ready(x)
                    return int(x.sum())
        """), "src/repro/serving/engine.py")
        assert fs == []

    def test_clean_same_function_other_file(self):
        fs = lint_source(_src("""
            def dispatch_decode(x):
                return int(x.sum())
        """), "src/repro/serving/metrics.py")
        assert fs == []


# ---------------------------------------------------------------------------
# suppression pragmas
# ---------------------------------------------------------------------------


class TestSuppression:
    def test_justified_pragma_suppresses(self):
        fs = lint_source(_src("""
            def f(x):
                assert x  # repro: allow[RPR104] test helper, -O never used here
        """), "src/repro/kernels/x.py")
        assert fs == []

    def test_pragma_on_line_above(self):
        fs = lint_source(_src("""
            def f(x):
                # repro: allow[RPR104] test helper, -O never used here
                assert x
        """), "src/repro/kernels/x.py")
        assert fs == []

    def test_unjustified_pragma_reports_rpr100_and_keeps_finding(self):
        fs = lint_source(_src("""
            def f(x):
                assert x  # repro: allow[RPR104]
        """), "src/repro/kernels/x.py")
        assert sorted(_rules_of(fs)) == ["RPR100", "RPR104"]

    def test_wrong_rule_id_does_not_suppress(self):
        fs = lint_source(_src("""
            def f(x):
                assert x  # repro: allow[RPR101] not the right rule
        """), "src/repro/kernels/x.py")
        assert "RPR104" in _rules_of(fs)


# ---------------------------------------------------------------------------
# historical-bug regression fixtures: each reproduces the shape of a bug a
# past PR actually shipped, and each must drive the CLI to a nonzero exit.
# ---------------------------------------------------------------------------


HISTORICAL_BUGS = {
    # PR 5: every Server shared one import-time ServerConfig() default.
    "shared_default_config": (
        "src/repro/serving/server.py",
        """
        class Server:
            def __init__(self, config=ServerConfig()):
                self.config = config
        """,
        "RPR102",
    ),
    # PR 5: module-global rid counter — fresh servers continued the old
    # id sequence.
    "global_rid_counter": (
        "src/repro/serving/api.py",
        """
        _rid = 0
        def next_rid():
            global _rid
            _rid += 1
            return _rid
        """,
        "RPR103",
    ),
    # PR 5: a bare assert guarded double-finish; under -O the check
    # vanished and a double finish evicted the slot's new tenant.
    "stripped_assert_double_finish": (
        "src/repro/serving/scheduler.py",
        """
        def finish(self, rid):
            assert rid in self.running, rid
            self.running.remove(rid)
        """,
        "RPR104",
    ),
    # PR 9: zero-copy device_put aliased the live page-table mirror under
    # dispatch-ahead; the server mutated it before the step consumed it.
    "mirror_aliasing": (
        "src/repro/serving/engine.py",
        """
        def stage(self):
            return jnp.asarray(self.cache.page_table)
        """,
        "RPR105",
    ),
}


class TestHistoricalBugRegressions:
    @pytest.mark.parametrize("name", sorted(HISTORICAL_BUGS))
    def test_rule_catches_bug(self, name):
        path, code, rule = HISTORICAL_BUGS[name]
        assert rule in _rules_of(lint_source(_src(code), path))

    @pytest.mark.parametrize("name", sorted(HISTORICAL_BUGS))
    def test_cli_exits_nonzero(self, name, tmp_path, capsys):
        # The fixture file keeps its hazard-relevant logical path segments
        # (serving/...) so path-scoped rules apply.
        path, code, rule = HISTORICAL_BUGS[name]
        dst = tmp_path.joinpath(*path.split("/"))
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(_src(code))
        rc = cli.main([str(dst), "--no-contracts", "--no-tiles"])
        out = capsys.readouterr().out
        assert rc == 1
        assert rule in out

    def test_cli_exits_zero_on_clean_file(self, tmp_path, capsys):
        dst = tmp_path / "clean.py"
        dst.write_text("def f(x):\n    return x\n")
        rc = cli.main([str(dst), "--no-contracts", "--no-tiles"])
        assert rc == 0


def test_repo_lints_clean():
    """The acceptance criterion: the shipped tree has zero unsuppressed
    findings and every pragma carries a justification."""
    findings = rules.lint_paths(["src/repro"])
    assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# contracts
# ---------------------------------------------------------------------------

# One arch per decoder family the CB stack serves: dense attention,
# sliding-window attention, recurrent (xLSTM), MoE.
CONTRACT_ARCHS = [
    "granite-3-8b", "gemma2-2b", "xlstm-125m", "granite-moe-1b-a400m",
]


class TestContracts:
    @pytest.mark.parametrize("arch", CONTRACT_ARCHS)
    def test_arch_clean_xla(self, arch):
        v = contracts.check_arch(arch, backend="xla")
        assert v == [], "\n".join(str(x) for x in v)

    def test_pallas_interpret_traces_pallas_call(self):
        v = contracts.check_arch("gemma2-2b", backend="pallas_interpret")
        assert v == [], "\n".join(str(x) for x in v)

    def test_fp8_kv_variant(self):
        v = contracts.check_arch("granite-3-8b", fp8_kv=True)
        assert v == [], "\n".join(str(x) for x in v)

    def test_recurrent_arch_clean(self):
        v = contracts.check_arch("recurrentgemma-2b", backend="xla")
        assert v == [], "\n".join(str(x) for x in v)

    def test_hbm_budget_fires_when_tiny(self):
        v = contracts.check_arch("gemma2-2b", backend="xla",
                                 hbm_budget_bytes=1.0, steps=("decode",))
        assert any(x.contract == "hbm-budget" for x in v)

    def test_bucket_policy_clean(self):
        assert contracts.check_bucket_policy(4) == []
        assert contracts.check_bucket_policy(8) == []

    def test_jaxpr_has_pallas_call_negative(self):
        j = jax.make_jaxpr(lambda x: x * 2 + 1)(jnp.zeros((4,)))
        assert not contracts.jaxpr_has_pallas_call(j)

    def test_jaxpr_has_pallas_call_positive(self):
        from jax.experimental import pallas as pl

        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2

        def f(x):
            return pl.pallas_call(
                k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=True,
            )(x)

        j = jax.make_jaxpr(f)(jnp.zeros((8, 128), jnp.float32))
        assert contracts.jaxpr_has_pallas_call(j)

    def test_jaxpr_has_pallas_call_nested(self):
        from jax.experimental import pallas as pl

        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2

        def f(x):
            inner = pl.pallas_call(
                k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=True,
            )
            return jax.lax.cond(x.sum() > 0, inner, lambda y: y, x)

        j = jax.make_jaxpr(f)(jnp.zeros((8, 128), jnp.float32))
        assert contracts.jaxpr_has_pallas_call(j)


# ---------------------------------------------------------------------------
# tiles
# ---------------------------------------------------------------------------


def _fake_tuning(**overrides):
    """A module-like stand-in cloning the real tuning module's tables with
    selective corruption."""
    mod = types.SimpleNamespace(**{
        k: v for k, v in vars(tuning).items() if not k.startswith("__")
    })
    for k, v in overrides.items():
        setattr(mod, k, v)
    return mod


class TestTiles:
    def test_shipped_tables_clean(self):
        fs = tiles.validate_tuning_tables()
        assert fs == [], "\n".join(str(f) for f in fs)

    def test_discovery_finds_every_registered_table(self):
        found = set(tiles.discover_tables())
        assert set(tiles.GEMM_TABLES) <= found
        assert set(tiles.ATTN_TABLES) <= found

    def test_unknown_table_is_a_finding(self):
        mod = _fake_tuning(_NEW_BAND_HEURISTIC={1: (512, 256), 2: (256, 256)})
        fs = tiles.validate_tuning_tables(mod)
        assert any(f.table == "_NEW_BAND_HEURISTIC" for f in fs)

    def test_misaligned_lane_is_a_finding(self):
        bad = dict(tuning._HEURISTIC)
        bad[2] = (64, 100, 512)  # bn=100 not lane-aligned
        fs = tiles.validate_tuning_tables(_fake_tuning(_HEURISTIC=bad))
        assert any(
            f.table == "_HEURISTIC" and "lane" in f.detail for f in fs
        )

    def test_vmem_blowout_is_a_finding(self):
        bad = dict(tuning._HEURISTIC)
        bad[4] = (2048, 2048, 2048)
        fs = tiles.validate_tuning_tables(_fake_tuning(_HEURISTIC=bad))
        assert any(
            f.table == "_HEURISTIC" and "VMEM" in f.detail for f in fs
        )

    def test_missing_itemsize_is_a_finding(self):
        bad = {k: v for k, v in tuning._SKINNY_HEURISTIC.items() if k != 1}
        fs = tiles.validate_tuning_tables(_fake_tuning(_SKINNY_HEURISTIC=bad))
        assert any(
            f.table == "_SKINNY_HEURISTIC" and "byte-width" in f.detail
            for f in fs
        )

    def test_bk_monotonicity_violation_is_a_finding(self):
        # Make the skinny band's K tile shallower than the chunk band's.
        bad = dict(tuning._SKINNY_HEURISTIC)
        bk, bn = bad[2]
        bad[2] = (tuning.SUBLANE[2], bn)
        fs = tiles.validate_tuning_tables(_fake_tuning(_SKINNY_HEURISTIC=bad))
        assert any("shallower" in f.detail for f in fs)

    def test_fp8_decode_attn_doubling_is_checked(self):
        bad = dict(tuning._DECODE_ATTN_HEURISTIC)
        ppb, hb = bad[2]
        bad[1] = (ppb, hb)  # fp8 should double ppb; keeping it equal fires
        fs = tiles.validate_tuning_tables(
            _fake_tuning(_DECODE_ATTN_HEURISTIC=bad)
        )
        assert any("fp8" in f.detail for f in fs)

    def test_lane_misaligned_k_tile_is_a_finding(self):
        # The 192-deep batched-prefill K tile the TPU compiler refused:
        # bk is the x block's last dim, so the sublane check alone passed it.
        bad = {1: (384, 128), 2: (192, 128), 4: (192, 128)}
        fs = tiles.validate_tuning_tables(
            _fake_tuning(_BATCH_PREFILL_HEURISTIC=bad)
        )
        assert {f.entry for f in fs
                if f.table == "_BATCH_PREFILL_HEURISTIC"
                and "lane" in f.detail} == {"2", "4"}

    def test_sub_tile_decode_head_block_is_a_finding(self):
        # head_block=1 on an 8-KV-head pool: the TPU compiler refused it.
        bad = {1: (8, 1), 2: (4, 1), 4: (4, 1)}
        fs = tiles.validate_tuning_tables(
            _fake_tuning(_DECODE_ATTN_HEURISTIC=bad)
        )
        assert {f.entry for f in fs
                if f.table == "_DECODE_ATTN_HEURISTIC"
                and "head_block" in f.detail} == {"1", "2", "4"}

    def test_illegal_decode_head_block_clamp_is_a_finding(self):
        # A clamp that only enforces divisibility hands 6 of 12 KV heads
        # to the kernel: neither whole nor a multiple of 8.
        def divisor_clamp(ppb, hb, *, pages_per_slot, n_kv_heads, **_):
            hb = max(1, min(hb, n_kv_heads))
            while n_kv_heads % hb:
                hb -= 1
            return max(1, min(ppb, pages_per_slot)), hb

        mod = _fake_tuning(clamp_decode_attn_blocks=divisor_clamp)
        mod.decode_attn_blocks = lambda **kw: divisor_clamp(
            8, 8, pages_per_slot=kw["pages_per_slot"],
            n_kv_heads=kw["n_kv_heads"],
        )
        fs = tiles.validate_tuning_tables(mod)
        assert any(f.table == "decode_attn_blocks"
                   and f.entry.startswith("Hkv=12,") for f in fs)


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------


class TestCLI:
    def test_list_rules(self, capsys):
        assert cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in rules.RULES:
            assert rid in out

    def test_tiles_only_clean(self, capsys):
        assert cli.main(["--no-lint", "--no-contracts"]) == 0

    def test_contracts_single_arch(self, capsys):
        rc = cli.main([
            "--no-lint", "--no-tiles", "--archs", "gemma2-2b",
            "--backends", "xla",
        ])
        out = capsys.readouterr().out
        assert rc == 0
        assert "contracts: 0 violation(s)" in out
