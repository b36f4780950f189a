"""Bench regression gate (tools/bench_diff.py + bench.py --compare):
artifact-shape parsing, gated-delta semantics, CLI exit codes."""

import json

import pytest

from tools.bench_diff import (
    compare_runs,
    format_table,
    load_bench,
    main as diff_main,
)


def _combined(p50, eps, extra=None):
    doc = {
        "metric": "commit_p50_latency", "value": p50, "unit": "us",
        "p99_us": p50 * 2, "entries_per_sec": eps,
        "configs": {
            "c2_batched": {"p50_us": p50, "p99_us": p50 * 2,
                           "entries_per_sec": eps},
            "attribution": {"wall_us_per_tick": 5000.0},
        },
    }
    if extra:
        doc["configs"].update(extra)
    return doc


class TestLoadBench:
    def test_json_lines_stdout(self, tmp_path):
        p = tmp_path / "run.json"
        lines = [
            json.dumps({"leg": "c2_batched", "p50_us": 2.0,
                        "entries_per_sec": 1e6}),
            json.dumps({"leg": "overload", "goodput_eps": 12.0}),
            json.dumps(_combined(2.0, 1e6)),
        ]
        p.write_text("\n".join(lines) + "\n")
        legs = load_bench(str(p))
        assert legs["c2_batched"]["p50_us"] == 2.0
        assert legs["overload"]["goodput_eps"] == 12.0
        assert legs["headline"]["p50_us"] == 2.0

    def test_legs_only_no_combined(self, tmp_path):
        """A deadline- or externally-killed run has leg rows but no
        final combined object — its finished legs must still load."""
        p = tmp_path / "killed.json"
        p.write_text(json.dumps({"leg": "c2_batched", "p50_us": 3.0}))
        assert load_bench(str(p))["c2_batched"]["p50_us"] == 3.0

    def test_wrapper_with_parsed(self, tmp_path):
        p = tmp_path / "BENCH_r99.json"
        p.write_text(json.dumps({
            "n": 1, "cmd": "python bench.py", "rc": 0,
            "tail": "noise\n", "parsed": _combined(2.5, 9e5),
        }))
        legs = load_bench(str(p))
        assert legs["c2_batched"]["p50_us"] == 2.5

    def test_wrapper_parsed_null_falls_back_to_tail(self, tmp_path):
        p = tmp_path / "BENCH_rkill.json"
        tail = ("WARNING: noise\n"
                + json.dumps({"leg": "c4_slow", "p50_us": 7.0}) + "\n")
        p.write_text(json.dumps({
            "n": 1, "cmd": "x", "rc": 124, "tail": tail, "parsed": None,
        }))
        assert load_bench(str(p))["c4_slow"]["p50_us"] == 7.0

    def test_not_a_bench_artifact(self, tmp_path):
        p = tmp_path / "junk.json"
        p.write_text("not json at all")
        with pytest.raises(ValueError):
            load_bench(str(p))

    def test_wrapper_with_parsed_combined_object_loads(self, tmp_path):
        """The driver-style wrapper whose ``parsed`` holds the combined
        final object: legs come from its ``configs``."""
        p = tmp_path / "bench_wrapped.json"
        combined = {"metric": "commit_p50_latency", "value": 2.0,
                    "configs": {"c2_batched": {"p50_us": 2.0,
                                               "method": "device"}}}
        p.write_text(json.dumps({
            "n": 4, "cmd": "python bench.py", "rc": 0,
            "tail": json.dumps(combined), "parsed": combined,
        }))
        legs = load_bench(str(p))
        assert legs["c2_batched"]["p50_us"] == 2.0


class TestCompare:
    def _legs(self, p50, eps):
        return {"c2_batched": {"p50_us": p50, "entries_per_sec": eps}}

    def test_no_regression_within_threshold(self):
        deltas, reg = compare_runs(self._legs(2.0, 1e6),
                                   self._legs(2.1, 0.96e6), 0.10)
        assert reg == []
        assert all(d.status in ("ok",) for d in deltas if d.gated)

    def test_latency_regression_gates(self):
        _, reg = compare_runs(self._legs(2.0, 1e6),
                              self._legs(2.5, 1e6), 0.10)
        assert [(d.leg, d.metric) for d in reg] == [
            ("c2_batched", "p50_us")]
        assert reg[0].change == pytest.approx(0.25)

    def test_throughput_regression_gates_in_the_down_direction(self):
        _, reg = compare_runs(self._legs(2.0, 1e6),
                              self._legs(2.0, 0.7e6), 0.10)
        assert [d.metric for d in reg] == ["entries_per_sec"]
        # and an IMPROVEMENT never gates
        deltas, reg2 = compare_runs(self._legs(2.0, 1e6),
                                    self._legs(1.0, 2e6), 0.10)
        assert reg2 == []
        assert {d.status for d in deltas if d.gated} == {"improved"}

    def test_added_removed_skipped_never_gate(self):
        old = {"a": {"p50_us": 1.0}, "gone": {"p50_us": 1.0},
               "skip": {"p50_us": 1.0}}
        new = {"a": {"p50_us": 1.0}, "fresh": {"p50_us": 9.0},
               "skip": {"skipped": "deadline"}}
        deltas, reg = compare_runs(old, new, 0.10)
        assert reg == []
        statuses = {(d.leg, d.status) for d in deltas}
        assert ("fresh", "added") in statuses
        assert ("gone", "removed") in statuses
        assert ("skip", "skipped") in statuses

    def test_ungated_metrics_ignored(self):
        old = {"x": {"mystery_number": 1.0}}
        new = {"x": {"mystery_number": 100.0}}
        deltas, reg = compare_runs(old, new, 0.10)
        assert reg == [] and all(not d.gated for d in deltas)

    def test_macro_leg_gates(self):
        """The round-14 macro (wire) columns: e2e latency gates DOWN,
        the batched-ingest amortization ratio gates UP, and shed_rate
        is deliberately ungated (at 2x capacity shedding is the
        designed behavior, not a regression axis)."""
        old = {"macro_wire": {
            "e2e_p50_ms": 10.0, "e2e_p99_ms": 20.0,
            "wire_goodput_ratio": 0.85, "shed_rate": 0.0,
        }}
        worse = {"macro_wire": {
            "e2e_p50_ms": 15.0, "e2e_p99_ms": 30.0,
            "wire_goodput_ratio": 0.60, "shed_rate": 0.9,
        }}
        _, reg = compare_runs(old, worse, 0.10)
        assert {(d.metric, d.status) for d in reg} == {
            ("e2e_p50_ms", "regressed"),
            ("e2e_p99_ms", "regressed"),
            ("wire_goodput_ratio", "regressed"),
        }
        # shed_rate moved 0 -> 0.9 and did not gate
        assert all(d.metric != "shed_rate" for d in reg)
        # and improvements never gate
        better = {"macro_wire": {
            "e2e_p50_ms": 5.0, "e2e_p99_ms": 9.0,
            "wire_goodput_ratio": 1.0, "shed_rate": 0.0,
        }}
        _, reg2 = compare_runs(old, better, 0.10)
        assert reg2 == []

    def test_wire_trace_leg_gates(self):
        """The round-15 trace-plane columns: the tracing-overhead ratio
        and the pump attribution coverage both gate UP (the <= 5%
        budget and the phases-tile-the-pump contract); the per-phase
        walls and percentiles ride ungated."""
        old = {"macro_wire_traced": {
            "tracing_overhead_ratio": 0.97, "pump_coverage": 0.99,
            "coalesce_batch_p99": 15.0, "queue_age_p99_us": 1500.0,
        }}
        worse = {"macro_wire_traced": {
            "tracing_overhead_ratio": 0.80, "pump_coverage": 0.60,
            "coalesce_batch_p99": 64.0, "queue_age_p99_us": 9000.0,
        }}
        _, reg = compare_runs(old, worse, 0.10)
        assert {(d.metric, d.status) for d in reg} == {
            ("tracing_overhead_ratio", "regressed"),
            ("pump_coverage", "regressed"),
        }
        _, reg2 = compare_runs(worse, old, 0.10)
        assert reg2 == []                   # improvements never gate

    def test_format_table_mentions_threshold(self):
        deltas, _ = compare_runs(self._legs(2.0, 1e6),
                                 self._legs(2.5, 1e6), 0.10)
        table = format_table(deltas, 0.10)
        assert "p50_us" in table and "10%" in table
        assert "1 regression(s)" in table


class TestCli:
    def _write(self, tmp_path, name, doc):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return str(p)

    def test_exit_zero_clean(self, tmp_path, capsys):
        old = self._write(tmp_path, "old.json", _combined(2.0, 1e6))
        new = self._write(tmp_path, "new.json", _combined(2.05, 1e6))
        assert diff_main([old, new]) == 0
        assert "no regressions" in capsys.readouterr().out

    def test_exit_one_on_regression(self, tmp_path, capsys):
        old = self._write(tmp_path, "old.json", _combined(2.0, 1e6))
        new = self._write(tmp_path, "new.json", _combined(3.0, 1e6))
        assert diff_main([old, new]) == 1
        assert "regressed" in capsys.readouterr().out

    def test_threshold_flag(self, tmp_path):
        old = self._write(tmp_path, "old.json", _combined(2.0, 1e6))
        new = self._write(tmp_path, "new.json", _combined(2.4, 1e6))
        assert diff_main([old, new]) == 1                  # 20% > 10%
        assert diff_main([old, new, "--threshold", "0.5"]) == 0


class TestCompileMemoryColumns:
    """ISSUE 11: the compile-&-memory plane columns gate (down), and a
    leg that NEWLY started recompiling is always reported + gated."""

    def test_newly_recompiling_leg_gates_and_is_named(self):
        old = {"steady": {"compile_count": 0,
                          "mem_high_water_bytes": 1000}}
        new = {"steady": {"compile_count": 2,
                          "mem_high_water_bytes": 1000}}
        deltas, regressions = compare_runs(old, new, 0.10)
        assert [(d.metric, d.status) for d in regressions] == [
            ("compile_count", "recompiling")
        ]
        table = format_table(deltas, 0.10)
        assert "legs newly recompiling" in table
        assert "steady" in table

    def test_mem_high_water_gates_down_and_improvement_passes(self):
        old = {"steady": {"compile_count": 4,
                          "mem_high_water_bytes": 1000}}
        worse = {"steady": {"compile_count": 4,
                            "mem_high_water_bytes": 1500}}
        better = {"steady": {"compile_count": 0,
                             "mem_high_water_bytes": 800}}
        _, reg = compare_runs(old, worse, 0.10)
        assert [d.metric for d in reg] == ["mem_high_water_bytes"]
        _, reg = compare_runs(old, better, 0.10)
        assert reg == []

    def test_old_artifact_without_columns_does_not_gate(self):
        """Artifacts older than the columns: their absence must read as
        "not measured", never as a regression."""
        old = {"steady": {"p50_us": 2.0}}
        new = {"steady": {"p50_us": 2.0, "compile_count": 7,
                          "mem_high_water_bytes": 123456}}
        deltas, reg = compare_runs(old, new, 0.10)
        assert reg == []
        assert all(d.metric != "compile_count" for d in deltas)
