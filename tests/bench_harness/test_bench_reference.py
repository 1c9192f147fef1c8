"""``correct`` at a size a test run can hold, on the CPU: the program agrees
with the plain reference inside the limits; the control (the reference one
precision step down, put in the program's place) falls outside them; and a
run whose timed path is broken underneath comes out ``correct: false``.
These tests skip the harness's look for a chip and drive the rest of a run.
"""

import ast
import os

import numpy as np
import pytest

from benchmarks import control, harness

import bench_tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_reference_imports_nothing_of_the_program():
    for rel in ("benchmarks/reference/two_tower_ref.py",
                "benchmarks/seeded_data.py", "benchmarks/loadgen.py",
                "benchmarks/trace_reduce.py"):
        with open(os.path.join(bench_tiny.ROOT, rel)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            assert not any(n.startswith("incubator_predictionio_tpu")
                           for n in names), (rel, names)


@pytest.mark.parametrize("name", ["tiny-two.serve-steady",
                                  "tiny-exact.serve-steady"])
def test_serving_cell_agrees_with_the_reference(root, name):
    line = bench_tiny.run_cell(root, name, seed=2_147_483_659, seconds=2.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 300
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_within_limit_pct",
                                    "serve_qps", "setup_s"}
    # answers completed inside the window: all but those in flight at its
    # end (how many says nothing on a loaded test host)
    assert 100.0 < line["metrics"]["serve_qps"]["value"] <= 150.0
    # a share of all requests; how large says nothing on a loaded test host
    assert 0.0 < line["metrics"]["serve_within_limit_pct"]["value"] <= 100.0
    assert line["device"]["platform"] == "cpu"  # a test, never a result


@pytest.mark.parametrize("name", ["tiny-two.serve-steady",
                                  "tiny-exact.serve-steady"])
def test_serving_control_falls_outside_the_limits(root, name):
    cell = harness.resolve_cell(name, root)
    # the two-stage cell does not compare regret_max (an IVF miss costs the
    # spacing of the next scores); its control has to fail the others
    assert ("regret_max" in cell.traffic["limits"]) == ("exact" in name)
    for seed in (1, 2, 3):
        numbers = control.serve_numbers(cell, seed)
        assert control.fails(cell, numbers), numbers


def test_serving_answer_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    from incubator_predictionio_tpu.models import two_tower

    real = two_tower.TwoTowerMF.recommend_batch

    def altered(model, user_idx, num, *a, **kw):
        idx, scores = real(model, user_idx, num, *a, **kw)
        idx = np.array(idx)
        idx[:, 0] = (idx[:, 0] + 7919) % model.n_items  # one item of ten
        return idx, scores

    monkeypatch.setattr(two_tower.TwoTowerMF, "recommend_batch",
                        staticmethod(altered))
    line = bench_tiny.run_cell(root, "tiny-exact.serve-steady", seed=5,
                               seconds=1.0)
    assert line["correct"] is False and line["failed"] == 0


def test_training_cell_agrees_with_the_reference(root):
    line = bench_tiny.run_cell(root, "tiny-train.train-verb", seed=7,
                               seconds=0.5, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert {"workflow_nonfit_s", "trainer_step_ms",
            "trainer_stage_init_s"} <= set(line["metrics"])
    assert line["device"]["window_s"] > 0


def test_training_control_falls_outside_the_limits(root):
    cell = harness.resolve_cell("tiny-train.train-verb", root)
    for seed in (1, 2, 3):
        numbers = control.train_numbers(cell, seed)
        assert "dnorm_gap" in control.fails(cell, numbers), numbers


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_training_step_broken_underneath_is_not_correct(
        root, monkeypatch, fault):
    from incubator_predictionio_tpu.models import two_tower

    real = two_tower._train_epochs

    def broken(p, o, ub, ib, rb, wb, lr, reg, n_epochs):
        if fault == "state_unchanged":
            # the real call donates its arguments: keep copies to hand back
            p0, o0 = two_tower.jax.tree.map(lambda x: x + 0, (p, o))
            _, _, loss = real(p, o, ub, ib, rb, wb, lr, reg, n_epochs)
            return p0, o0, loss
        half = wb.at[:, : wb.shape[1] // 2].set(0.0)
        return real(p, o, ub, ib, rb, half, lr, reg, n_epochs)

    monkeypatch.setattr(two_tower, "_train_epochs", broken)
    line = bench_tiny.run_cell(root, "tiny-train.train-verb", seed=9,
                               seconds=0.3)
    assert line["correct"] is False
