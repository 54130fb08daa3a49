"""Roofline cost model tests: extraction stability, the peaks table and
its env overrides, gauge publication at the heartbeat seam, registry
persistence of cost records, and the cost/profile CLI verbs."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from katib_tpu import costmodel
from katib_tpu.compile.registry import CompileSignature, ShapeRegistry
from katib_tpu.costmodel import live as cm_live
from katib_tpu.costmodel import peaks as cm_peaks
from katib_tpu.costmodel import profiler as cm_profiler
from katib_tpu.costmodel.record import CostRecord, cost_of_compiled
from katib_tpu.utils import observability as obs


@jax.jit
def _matmul_step(x, w):
    return jnp.tanh(x @ w)


def _avals():
    return (
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
    )


class TestExtraction:
    def test_extract_cost_from_avals_no_device_data(self):
        rec = costmodel.extract_cost(
            _matmul_step, _avals(), program="p", steps=4, dtype="f32"
        )
        assert rec is not None
        assert rec.flops > 0
        assert rec.bytes_accessed > 0
        assert rec.flops_per_step == rec.flops / 4
        assert rec.arithmetic_intensity > 0

    def test_stable_across_two_lowerings(self):
        a = costmodel.extract_cost(_matmul_step, _avals(), program="p")
        b = costmodel.extract_cost(_matmul_step, _avals(), program="p")
        assert a is not None and b is not None
        assert (a.flops, a.bytes_accessed) == (b.flops, b.bytes_accessed)

    def test_cost_of_compiled_reports_hbm(self):
        compiled = jax.jit(lambda x, w: x @ w).lower(*_avals()).compile()
        rec = cost_of_compiled(compiled, program="p")
        assert rec is not None
        assert rec.flops > 0
        assert rec.hbm_bytes > 0  # argument+output+temp+code bytes

    def test_extraction_failure_returns_none(self):
        assert costmodel.extract_cost(object(), ()) is None

    def test_roundtrip_as_dict(self):
        rec = CostRecord(
            program="p", flops=100.0, bytes_accessed=50.0, hbm_bytes=7,
            steps=2, dtype="f32",
        )
        again = CostRecord.from_dict(json.loads(json.dumps(rec.as_dict())))
        assert again == rec


class TestPeaks:
    @pytest.mark.parametrize(
        "raw, key",
        [("TPU v5 lite", "v5e"), ("v5e", "v5e"), ("TPU v5p", "v5p"),
         ("TPU v4", "v4"), ("TPU v3", "v3")],
    )
    def test_normalize_device_kind(self, raw, key):
        assert cm_peaks.normalize_device_kind(raw) == key

    @pytest.mark.parametrize("raw", ["cpu", "TPU v9000", "", None])
    def test_unknown_device_kind_raises(self, raw):
        # a device that is not in the table is an error, not a default
        with pytest.raises(cm_peaks.UnknownDeviceKind):
            cm_peaks.normalize_device_kind(raw)
        with pytest.raises(cm_peaks.UnknownDeviceKind):
            cm_peaks.peaks_for(raw if raw is not None else "None")

    def test_live_device_on_the_cpu_has_no_peaks(self):
        # the tests run on the CPU backend: no row, so no MFU denominator
        with pytest.raises(cm_peaks.UnknownDeviceKind):
            cm_peaks.peaks_for()

    def test_peak_flops_dtype_fallback(self):
        pk = cm_peaks.PEAKS["v5e"]
        assert pk.peak_flops("bf16") == 197e12
        assert pk.peak_flops("f32") == 98.5e12
        assert pk.peak_flops("no-such-dtype") == 197e12  # bf16 fallback

    def test_environment_cannot_move_the_peaks(self, monkeypatch):
        monkeypatch.setenv("KATIB_PEAK_FLOPS", "1e12")
        monkeypatch.setenv("KATIB_PEAK_BW", "2e11")
        pk = cm_peaks.peaks_for("v5e")
        assert pk.peak_flops("bf16") == 197e12
        assert pk.hbm_bandwidth == 819e9

    def test_roofline_classification(self):
        pk = cm_peaks.DevicePeaks(
            "x", {"bf16": 100.0}, hbm_bandwidth=10.0, hbm_bytes=1
        )
        # intensity 1 flop/byte < ridge 10 -> memory bound
        mem = CostRecord(flops=10.0, bytes_accessed=10.0).roofline(pk)
        assert mem["bound"] == "memory-bound"
        assert mem["max_mfu"] == pytest.approx(0.1)
        # intensity 100 > ridge -> compute bound, ceiling 1.0
        comp = CostRecord(flops=100.0, bytes_accessed=1.0).roofline(pk)
        assert comp["bound"] == "compute-bound"
        assert comp["max_mfu"] == pytest.approx(1.0)


class _FakeJit:
    """Counts lowerings; returns a fixed cost analysis."""

    def __init__(self):
        self.lowerings = 0

    def lower(self, *args):
        self.lowerings += 1
        outer = self

        class _L:
            def cost_analysis(self):
                return {"flops": 10.0, "bytes accessed": 5.0}

        return _L()


class TestLiveSlot:
    def setup_method(self):
        cm_live.clear_active()

    def test_observe_arms_slot_and_memoizes(self):
        fn = _FakeJit()
        label = ("prog", 8, "mesh")
        rec = cm_live.observe_program(label, fn, (), program="p", per_report=3)
        assert rec is not None and rec.flops == 10.0
        assert cm_live.active_cost() == (rec, 3)
        cm_live.observe_program(label, fn, (), program="p", per_report=3)
        assert fn.lowerings == 1  # second observation was a memo hit

    def test_none_label_skips_memo(self):
        fn = _FakeJit()
        cm_live.observe_program(None, fn, (), program="p")
        cm_live.observe_program(None, fn, (), program="p")
        assert fn.lowerings == 2

    def test_clear_active_disarms(self):
        cm_live.set_active_cost(CostRecord(flops=1.0), per_report=2)
        assert cm_live.active_cost() is not None
        cm_live.clear_active()
        assert cm_live.active_cost() is None
        assert cm_live.span_attrs() == {}

    def test_publish_dispatch_sets_gauges_and_attrs(self):
        pk = cm_peaks.DevicePeaks(
            "testkind", {"bf16": 100.0}, hbm_bandwidth=10.0, hbm_bytes=1
        )
        rec = CostRecord(program="p", flops=50.0, bytes_accessed=1.0)
        attrs = cm_live.publish_dispatch(
            rec, 1.0, workload="wl-publish", peaks=pk
        )
        assert attrs["mfu"] == pytest.approx(0.5)
        assert attrs["roofline"] == "compute-bound"
        assert cm_live.span_attrs() == attrs
        assert obs.dispatch_mfu.get(
            workload="wl-publish", device_kind="testkind", dtype="bf16"
        ) == pytest.approx(0.5)
        assert obs.arithmetic_intensity.get(workload="wl-publish") == 50.0
        assert obs.roofline_headroom.get(
            workload="wl-publish", bound="compute-bound"
        ) == pytest.approx(2.0)  # 1.0s measured vs 0.5s compute floor

    def test_publish_dispatch_publishes_nothing_on_the_cpu(self):
        # no peaks passed: the live device is the CPU backend, which the
        # table does not hold — no gauge, no attrs, no exception
        rec = CostRecord(program="p", flops=50.0, bytes_accessed=1.0)
        assert cm_live.publish_dispatch(rec, 1.0, workload="wl-cpu") == {}
        assert not [
            labels
            for labels, _v in obs.dispatch_mfu.samples()
            if labels.get("workload") == "wl-cpu"
        ]

    def test_publish_dispatch_rejects_zero_time(self):
        assert cm_live.publish_dispatch(
            CostRecord(flops=1.0), 0.0, workload="x"
        ) == {}
        assert cm_live.publish_dispatch(
            CostRecord(flops=0.0), 1.0, workload="x"
        ) == {}


class TestRegistryCost:
    def test_record_cost_idempotent_and_readable(self):
        reg = ShapeRegistry()
        sig = CompileSignature(program="cost_prog", k=2)
        cost = CostRecord(program="cost_prog", flops=9.0).as_dict()
        assert reg.record_cost(sig, cost) is True
        assert reg.record_cost(sig, cost) is False  # unchanged: no-op
        assert reg.cost_of(sig) == cost
        # the synthesized row shows up in signatures() with source=cost
        rows = [r for r in reg.signatures() if r["program"] == "cost_prog"]
        assert rows and rows[0]["source"] == "cost"

    def test_cost_persists_and_reloads(self, tmp_path, monkeypatch):
        import katib_tpu.compile.registry as regmod

        monkeypatch.setattr(regmod, "_cache_dir", lambda: str(tmp_path))
        reg = ShapeRegistry()
        sig = CompileSignature(program="persist_prog", k=1)
        reg.record(sig, source="trial", compile_seconds=0.1)
        cost = CostRecord(program="persist_prog", flops=3.0, steps=2).as_dict()
        assert reg.record_cost(sig, cost) is True
        # a fresh registry over the same dir folds the cost-bearing line
        fresh = ShapeRegistry()
        assert fresh.cost_of(sig) == cost
        row = [r for r in fresh.signatures() if r["program"] == "persist_prog"][0]
        assert row["source"] == "trial"  # identity fields keep the first record


class TestHeartbeatPublication:
    def test_run_trial_persists_cost_and_publishes_no_cpu_mfu(self):
        from katib_tpu.compile.registry import REGISTRY
        from katib_tpu.core.types import (
            ObjectiveSpec,
            ObjectiveType,
            ParameterAssignment,
            Trial,
            TrialCondition,
            TrialSpec,
        )
        from katib_tpu.runner.trial_runner import run_trial
        from katib_tpu.store.base import MemoryObservationStore

        def costed_trainer(ctx):
            costmodel.set_active_cost(
                CostRecord(program="costed_trainer", flops=1e9), per_report=1
            )
            for step in range(3):
                time.sleep(0.01)
                if not ctx.report(accuracy=0.5 + step / 10, step=step):
                    return

        trial = Trial(
            name="cost-t1",
            spec=TrialSpec(
                assignments=[ParameterAssignment("x", 1.0)],
                train_fn=costed_trainer,
            ),
        )
        objective = ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
        )
        try:
            res = run_trial(trial, MemoryObservationStore(), objective)
            assert res.condition == TrialCondition.SUCCEEDED
            # the heartbeats publish MFU against the live device's peak;
            # this run is on the CPU, which has none — nothing is published
            # (the workload label is the train_fn's qualname)
            mine = [
                v
                for labels, v in obs.dispatch_mfu.samples()
                if labels.get("workload", "").endswith("costed_trainer")
            ]
            assert mine == []
            # the cost landed next to the trial's compile signature
            rows = [
                r
                for r in REGISTRY.signatures()
                if r["program"].endswith("costed_trainer")
            ]
            assert rows and rows[0]["cost"]["flops"] == 1e9
        finally:
            REGISTRY.reset()

    def test_executor_thread_reuse_does_not_leak_cost(self):
        # clear_active at trial start: a second trial on the same thread
        # without its own observation publishes nothing
        cm_live.set_active_cost(CostRecord(flops=1.0))
        from katib_tpu.core.types import (
            ObjectiveSpec,
            ObjectiveType,
            ParameterAssignment,
            Trial,
            TrialCondition,
            TrialSpec,
        )
        from katib_tpu.runner.trial_runner import run_trial
        from katib_tpu.store.base import MemoryObservationStore

        def plain_trainer(ctx):
            assert costmodel.active_cost() is None
            ctx.report(accuracy=1.0, step=0)

        trial = Trial(
            name="cost-t2",
            spec=TrialSpec(
                assignments=[ParameterAssignment("x", 1.0)],
                train_fn=plain_trainer,
            ),
        )
        objective = ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
        )
        res = run_trial(trial, MemoryObservationStore(), objective)
        assert res.condition == TrialCondition.SUCCEEDED


class TestProfiler:
    def setup_method(self):
        cm_profiler.reset()

    def test_capture_registers_and_writes(self, tmp_path):
        out = tmp_path / "exp" / "t0" / "profile"
        with cm_profiler.capture(str(out), trial="t0", experiment="exp"):
            jax.block_until_ready(_matmul_step(jnp.ones((8, 16)), jnp.ones((16, 16))))
        entries = cm_profiler.list_profiles()
        assert len(entries) == 1
        assert entries[0]["trial"] == "t0"
        assert os.path.isdir(out)

    def test_scan_profiles_finds_trial_dirs(self, tmp_path):
        d = tmp_path / "exp-a" / "trial-3" / "profile"
        os.makedirs(d)
        entries = cm_profiler.scan_profiles(str(tmp_path))
        assert [e["trial"] for e in entries] == ["trial-3"]
        assert entries[0]["experiment"] == "exp-a"

    def test_scan_profiles_reads_journal_spans(self, tmp_path):
        from katib_tpu.utils import tracing

        expdir = tmp_path / "exp-b"
        os.makedirs(expdir)
        rec = {
            "name": cm_profiler.PROFILE_SPAN,
            "ts": 0.0,
            "dur": 1.0,
            "args": {"trial": "t7", "trace_dir": str(tmp_path / "elsewhere")},
        }
        (expdir / tracing.TRACE_FILE).write_text(json.dumps(rec) + "\n")
        entries = cm_profiler.scan_profiles(str(tmp_path))
        assert entries and entries[0]["trial"] == "t7"
        assert entries[0]["source"] == "journal"


class TestCliVerbs:
    def test_cost_on_empty_dir_fails_cleanly(self, tmp_path, capsys):
        from katib_tpu.cli import main

        assert main(["cost", str(tmp_path)]) == 1
        assert "no cost records" in capsys.readouterr().err

    def test_cost_on_registry_dir_prints_table(self, tmp_path, capsys):
        from katib_tpu.cli import main

        sig = CompileSignature(program="tbl_prog", k=2)
        row = {
            "key": sig.key(),
            "program": "tbl_prog",
            "k": 2,
            "mesh": "",
            "shapes": {},
            "donation": True,
            "source": "trial",
            "cost": CostRecord(
                program="tbl_prog", flops=2e9, bytes_accessed=1e8, steps=2
            ).as_dict(),
        }
        (tmp_path / "shape_registry.jsonl").write_text(json.dumps(row) + "\n")
        assert main(["cost", str(tmp_path), "--device", "v5e"]) == 0
        out = capsys.readouterr().out
        assert "tbl_prog" in out
        assert "roofline vs v5e" in out

    def test_profile_list_empty_ok(self, tmp_path, capsys):
        from katib_tpu.cli import main

        assert main(["profile", "--list", "--workdir", str(tmp_path)]) == 0
        assert "no profiler captures" in capsys.readouterr().out

    def test_profile_without_target_is_usage_error(self, capsys):
        from katib_tpu.cli import main

        assert main(["profile"]) == 2

    def test_trace_summary_top_surfaces_cost_attrs(self, tmp_path, capsys):
        from katib_tpu.cli import main
        from katib_tpu.utils import tracing

        expdir = tmp_path / "exp-c"
        os.makedirs(expdir)
        recs = [
            {
                "name": "trial",
                "ts": 0.0,
                "dur": 2.5,
                "args": {
                    "trial": "t1",
                    "mfu": 0.1234,
                    "roofline": "memory-bound",
                    "roofline_headroom": 4.0,
                },
            },
            {"name": "suggest", "ts": 0.0, "dur": 0.01},
        ]
        (expdir / tracing.TRACE_FILE).write_text(
            "".join(json.dumps(r) + "\n" for r in recs)
        )
        assert main(
            ["trace", "summary", "exp-c", "--workdir", str(tmp_path), "--top", "2"]
        ) == 0
        out = capsys.readouterr().out
        assert "memory-bound" in out
        assert "0.1234" in out
