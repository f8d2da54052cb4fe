"""CPU rehearsal of the harness at a tiny size: both drive modes, the
result line, the check against the reference, its control and planted
faults, a served system of two rules added as files only, and the refusal
to measure without a TPU.

The rehearsal calls ``harness.run_cell`` with ``require_tpu=False``; the
command itself (``run.py``) always requires the chip.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from cepbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 4242
TINY = {"config": {"partitions": 4,
                   "runtime": {"buffer_capacity": 128,
                               "match_capacity": 512,
                               "chunk_capacity": 128,
                               "backend": "interpret"}},
        "mix": {"pool_slices": 16, "warmup_slices": 2}}
REPLAY = "seq4_tree_k16.traffic_replay"
LIVE = "seq4_order_k16.stocks_live"


def run(cell=REPLAY, seconds=1.0, traced=False, factory=None, mix=None):
    patch = {"config": TINY["config"], "mix": dict(TINY["mix"],
                                                   **(mix or {}))}
    return harness.run_cell(ROOT, cell, SEED, seconds, traced,
                            t_start=time.perf_counter(),
                            system_factory=factory, require_tpu=False,
                            patch=patch)


def test_replay_run_is_correct_and_well_formed():
    out = run()
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    # One entry per answered slice (warm-up included) and partition.
    assert out["attempted"] == (out["window"]["slices"] + 2) * 4
    assert set(out["metrics"]) == {"events_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(v == {"value": 0, "limit": 0}
               for v in out["compared"].values())
    json.dumps(out)


def test_live_run_is_correct_and_reports_latency():
    out = run(LIVE, seconds=3.0, mix={"base_rate": 8.0})
    assert out["correct"] is True
    assert set(out["metrics"]) == {"detect_p95_ms", "setup_s"}
    # Events wait for the end of their 1-s slice.
    assert out["metrics"]["detect_p95_ms"]["value"] > 500.0


def test_traced_run_reports_per_layer_metrics_only():
    out = run(traced=True)
    assert out["correct"] is True
    assert "replans_per_1k_events.replay" in out["metrics"]
    assert "events_per_s" not in out["metrics"]


def test_silent_per_layer_metric_fails_the_run():
    cell = harness.load_cell(ROOT, REPLAY)
    names = [m["name"] for m in cell.per_layer]
    assert names
    with pytest.raises(SystemExit, match=names[-1]):
        harness.require_all(cell, {n: {} for n in names[:-1]})
    harness.require_all(cell, {n: {} for n in names})


def test_control_is_not_correct():
    out = run(factory=harness.Control)
    assert out["correct"] is False
    assert out["compared"]["count_mismatches"]["value"] > 0


class AlteredAnswer(harness.Program):
    """One partition's count of one slice is off by one."""

    calls = 0

    def process(self, *args):
        out = np.array(super().process(*args))
        self.calls += 1
        if self.calls == 3:
            out[1] += 1
        return out


class HalfBatch(harness.Program):
    """Every other event of the batch is left out."""

    def process(self, tid, ts, attr, keys, t0, t1):
        keep = np.arange(len(ts)) % 2 == 0
        return super().process(tid[keep], ts[keep], attr[keep], keys[keep],
                               t0, t1)


class StateUnchanged(harness.Program):
    """The step hands back the ring buffers it was given."""

    def process(self, *args):
        eng = self.session._ensure_serving()
        before = eng.state
        out = super().process(*args)
        eng.state = before
        return out


@pytest.mark.parametrize("fault", [AlteredAnswer, HalfBatch,
                                   StateUnchanged])
def test_planted_fault_is_not_correct(fault):
    out = run(factory=fault)
    assert out["correct"] is False
    assert out["compared"]["count_mismatches"]["value"] > 0


def test_unknown_system_fails_setup():
    with pytest.raises(SystemExit, match="known: .*'session'"):
        harness.run_cell(ROOT, REPLAY, SEED, 1.0, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         patch={"config": dict(TINY["config"],
                                               system="no_such_system"),
                                "mix": TINY["mix"]})


def test_count_mismatches_entry_by_entry():
    want = np.array([[1, 2], [3, 4], [5, 6]])
    assert harness.count_mismatches(want.copy(), want) == 0
    got = want.copy()
    got[1, 0] += 1
    assert harness.count_mismatches(got, want) == 1
    assert harness.count_mismatches(want.T, want) == 6
    assert harness.count_mismatches(want[:, 0], want) == 6
    assert harness.count_mismatches(np.array([1, 2, 4]),
                                    np.array([1, 2, 3])) == 1


# A served system of two rules, added as files only: a configuration, a
# system, a pattern kind and its reference (``two_rules/``), and a cell
# entry, in a copy of the benchmark.
RULES = "two_rules.traffic_replay"


@pytest.fixture(scope="module")
def rules_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("rules")
    here = root / "cepbench"
    shutil.copytree(os.path.join(ROOT, "cepbench"), here,
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__", "tests"))
    fixture = os.path.join(os.path.dirname(__file__), "two_rules")
    for folder in os.listdir(fixture):
        for name in os.listdir(os.path.join(fixture, folder)):
            shutil.copy(os.path.join(fixture, folder, name), here / folder)
    bench = harness._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "two_rules", "source": "test",
                             "file": "cepbench/configs/two_rules.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": RULES, "config": "two_rules",
                               "traffic": "traffic_replay", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append(RULES)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def run_rules(root, factory=None):
    return harness.run_cell(root, RULES, SEED, 1.0, False,
                            t_start=time.perf_counter(),
                            system_factory=factory, require_tpu=False,
                            patch={"config": {"runtime": TINY["config"][
                                "runtime"]}, "mix": TINY["mix"]})


def _answers(change):
    """The configuration's own system, with ``change(call, out)`` applied
    to each answer."""

    def factory(config, here):
        system = harness.open_system(config, here)
        inner = system.process
        calls = []

        def process(*args):
            calls.append(None)
            return change(len(calls), np.array(inner(*args)))

        system.process = process
        return system

    return factory


def test_rule_vector_system_is_correct(rules_root):
    out = run_rules(rules_root)
    assert out["correct"] is True and out["failed"] == 0
    # One entry per answered slice (warm-up included), partition and rule.
    assert out["attempted"] == (out["window"]["slices"] + 2) * 4 * 2
    assert all(v == {"value": 0, "limit": 0}
               for v in out["compared"].values())


def test_rule_vector_fault_in_one_rule_is_caught(rules_root):
    def one_rule(call, out):
        if call == 3:
            out[1, 1] += 1
        return out

    out = run_rules(rules_root, _answers(one_rule))
    assert out["correct"] is False
    assert out["compared"]["count_mismatches"]["value"] == 1
    assert out["failed"] == 1


def test_rule_vector_of_wrong_shape_is_not_correct(rules_root):
    out = run_rules(rules_root, _answers(lambda call, out: out[:, :1]))
    assert out["correct"] is False
    assert out["compared"]["count_mismatches"]["value"] == out["attempted"]


def test_rule_vector_control_is_not_correct(rules_root):
    out = run_rules(rules_root, harness.Control)
    assert out["correct"] is False
    assert out["compared"]["count_mismatches"]["value"] > 0


def _command(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "cepbench/run.py", "--workload", REPLAY,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_a_tpu():
    res = _command(ROOT, {})
    assert res.returncode != 0
    assert "needs 1 TPU" in res.stderr
    assert res.stdout.strip() == ""


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "cepbench"), tmp_path / "cepbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    res = _command(tmp_path, {"PYTHONPATH": ""})
    assert res.returncode != 0
    assert res.stdout.strip() == ""
