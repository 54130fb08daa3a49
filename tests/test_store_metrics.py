"""Observation store + metrics parser tests (parity: reference DB-manager
single-table contract kdb.go:23 and file-metricscollector parsing rules)."""

import threading

import pytest

from katib_tpu.core.types import (
    MetricLog,
    MetricStrategyType,
    ObjectiveSpec,
    ObjectiveType,
)
from katib_tpu.runner.metrics import (
    DEFAULT_TEXT_FILTER,
    objective_reported,
    parse_json_lines,
    parse_text_lines,
)
from katib_tpu.store.base import MemoryObservationStore
from katib_tpu.store.sqlite import SqliteObservationStore


OBJ = ObjectiveSpec(
    type=ObjectiveType.MAXIMIZE,
    objective_metric_name="accuracy",
    additional_metric_names=("loss",),
)


@pytest.fixture(params=["memory", "sqlite"])
def store(request):
    if request.param == "memory":
        yield MemoryObservationStore()
    else:
        s = SqliteObservationStore(":memory:")
        yield s
        s.close()


class TestStore:
    def test_report_get_roundtrip(self, store):
        store.report_point("t1", "accuracy", 0.5, step=0)
        store.report_point("t1", "accuracy", 0.7, step=1)
        store.report_point("t1", "loss", 1.2, step=1)
        logs = store.get("t1", "accuracy")
        assert [l.value for l in logs] == [0.5, 0.7]
        assert store.get("t1")[2].metric_name == "loss"
        assert store.get("t2") == []

    def test_delete(self, store):
        store.report_point("t1", "accuracy", 0.5)
        store.delete("t1")
        assert store.get("t1") == []

    def test_reduce_strategies(self, store):
        for v in [0.3, 0.9, 0.6]:
            store.report_point("t1", "accuracy", v)
        assert store.reduce("t1", "accuracy", MetricStrategyType.MAX) == 0.9
        assert store.reduce("t1", "accuracy", MetricStrategyType.MIN) == 0.3
        assert store.reduce("t1", "accuracy", MetricStrategyType.LATEST) == 0.6
        assert store.reduce("t1", "missing", MetricStrategyType.MAX) is None

    def test_observation_builds_with_strategies(self, store):
        for v in [0.3, 0.9, 0.6]:
            store.report_point("t1", "accuracy", v)
        for v in [2.0, 1.0]:
            store.report_point("t1", "loss", v)
        obs = store.observation_for("t1", OBJ)
        acc = obs.get("accuracy")
        assert acc.value == 0.9  # maximize -> max strategy
        assert acc.min == 0.3 and acc.max == 0.9 and acc.latest == 0.6
        assert obs.get("loss").value == 1.0  # additional metric -> latest

    def test_observation_none_when_objective_missing(self, store):
        store.report_point("t1", "loss", 1.0)
        assert store.observation_for("t1", OBJ) is None

    def test_threaded_reports(self, store):
        def worker(i):
            for j in range(50):
                store.report_point(f"t{i % 3}", "accuracy", float(j))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = sum(len(store.get(f"t{k}")) for k in range(3))
        assert total == 300


class TestMemoryBus:
    def test_subscription(self):
        store = MemoryObservationStore()
        seen = []
        store.subscribe(lambda trial, log: seen.append((trial, log.value)))
        store.report("t1", [MetricLog("accuracy", 0.5), MetricLog("accuracy", 0.6)])
        assert seen == [("t1", 0.5), ("t1", 0.6)]


class TestTextParser:
    def test_basic_pairs(self):
        logs = parse_text_lines(
            ["epoch 1 accuracy=0.81 loss=1.25", "noise line", "accuracy=0.92"],
            ["accuracy", "loss"],
        )
        assert [(l.metric_name, l.value) for l in logs] == [
            ("accuracy", 0.81),
            ("loss", 1.25),
            ("accuracy", 0.92),
        ]

    def test_timestamp_prefix(self):
        logs = parse_text_lines(
            ["2024-01-15T10:30:00Z accuracy=0.5"], ["accuracy"]
        )
        assert logs[0].timestamp > 0

    def test_untracked_metrics_dropped(self):
        logs = parse_text_lines(["accuracy=0.5 junk=1.0"], ["accuracy"])
        assert len(logs) == 1

    def test_scientific_notation(self):
        logs = parse_text_lines(["loss=1.5e-3"], ["loss"])
        assert logs[0].value == pytest.approx(1.5e-3)

    def test_custom_filter(self):
        # custom filter: "name: value" style instead of the default "name=value"
        logs = parse_text_lines(
            ["accuracy: 0.97 (epoch 3)", "accuracy=0.5 ignored by custom filter"],
            ["accuracy"],
            filters=[r"([\w|-]+):\s*([+-]?\d*(?:\.\d+)?)"],
        )
        assert [(l.metric_name, l.value) for l in logs] == [("accuracy", 0.97)]

    def test_default_filter_regex_matches_reference_format(self):
        import re

        m = re.search(DEFAULT_TEXT_FILTER, "Validation-Accuracy=0.9213")
        assert m.group(1) == "Validation-Accuracy"
        assert float(m.group(2)) == pytest.approx(0.9213)


class TestJsonParser:
    def test_basic(self):
        logs = parse_json_lines(
            ['{"accuracy": 0.8, "step": 3}', '{"loss": "1.5"}'],
            ["accuracy", "loss"],
        )
        assert logs[0].value == 0.8 and logs[0].step == 3
        assert logs[1].value == 1.5

    def test_timestamp_variants(self):
        logs = parse_json_lines(
            ['{"accuracy": 0.8, "timestamp": 1700000000.5}'], ["accuracy"]
        )
        assert logs[0].timestamp == pytest.approx(1700000000.5)
        logs = parse_json_lines(
            ['{"accuracy": 0.8, "timestamp": "2024-01-15T10:30:00Z"}'], ["accuracy"]
        )
        assert logs[0].timestamp > 0

    def test_invalid_json_raises(self):
        with pytest.raises(ValueError):
            parse_json_lines(["{not json"], ["accuracy"])

    def test_objective_reported(self):
        logs = parse_json_lines(['{"loss": 1.0}'], ["accuracy", "loss"])
        assert not objective_reported(logs, "accuracy")
        logs += parse_json_lines(['{"accuracy": 0.5}'], ["accuracy"])
        assert objective_reported(logs, "accuracy")


class TestDataSeedDeterminism:
    def test_synthetic_dataset_stable_across_processes(self):
        import subprocess
        import sys

        code = (
            "import sys; sys.path.insert(0, '/root/repo');"
            "from katib_tpu.models.data import load_mnist;"
            "ds = load_mnist(64, 16); print(float(ds.x_train.sum()))"
        )
        outs = set()
        for i in (1, 2):
            proc = subprocess.run(
                [sys.executable, "-c", code], capture_output=True, text=True,
                env={"PATH": "/usr/bin:/bin", "PYTHONHASHSEED": str(i),
                     "JAX_PLATFORMS": "cpu"},
            )
            assert proc.returncode == 0, proc.stderr
            float(proc.stdout.strip())  # a real checksum, not empty output
            outs.add(proc.stdout.strip())
        assert len(outs) == 1  # same dataset regardless of hash salt


class TestRealDigitsDataset:
    def test_load_digits_real_is_learnable_real_data(self):
        """sklearn's bundled UCI digits: real data, deterministic split,
        disjoint train/test, and a linear-ish model learns far above chance
        (the real-accuracy evidence path, scripts/run_real_data_demo.py)."""
        pytest.importorskip("sklearn")  # the bayesopt extra carries it
        from katib_tpu.models.data import load_digits_real

        ds = load_digits_real()
        assert ds.x_train.shape[1:] == (8, 8, 1)
        assert ds.num_classes == 10
        assert 0.0 <= ds.x_train.min() and ds.x_train.max() <= 1.0
        # deterministic split
        ds2 = load_digits_real()
        assert (ds.y_train == ds2.y_train).all()
        # train/test disjoint (row-level)
        train_keys = {r.tobytes() for r in ds.x_train.reshape(len(ds.x_train), -1)}
        dup = sum(
            1 for r in ds.x_test.reshape(len(ds.x_test), -1)
            if r.tobytes() in train_keys
        )
        assert dup <= 2  # UCI digits has a couple of literal duplicates

        from katib_tpu.models.mnist import MLP, train_classifier

        acc = train_classifier(
            MLP(units=64), ds, lr=0.1, epochs=5, batch_size=64,
            eval_batch=len(ds.x_test),
        )
        assert acc > 0.8, acc  # real-data learning, far above 10% chance
