"""Determinism contract of the parallel cell runner + seeded search.

* same seed ⇒ identical cell rows and identical Pareto fronts;
* a 2-worker process pool is **bit-identical** to the serial path —
  same floats, same result ordering (submission order, not completion
  order);
* a failing cell raises `CellError` naming the cell, and a hard worker
  death (``os._exit`` via the ``REPRO_SEARCH_TEST_CRASH`` hook) also
  surfaces as `CellError` instead of hanging the pool;
* pool workers are spawned and never import JAX, so none of them can
  inherit or take the accelerator a parent process holds.
"""
import pytest

from repro.search import (CellError, CellSpec, default_space, run_cells,
                          run_search)
from repro.search import runner
from repro.search.runner import _CRASH_ENV

# Small enough that the whole module stays in CI seconds; 2 scenario
# families × a handful of policy cells exercise scheduler, autoscaler,
# rescheduler and template axes.
N_JOBS = 40

CELLS = [
    CellSpec(scenario="diurnal", scheduler="best-fit", autoscaler="binding",
             rescheduler="non-binding", seed=3, n_jobs=N_JOBS),
    CellSpec(scenario="heavy-tail", scheduler="weighted",
             autoscaler="non-binding", rescheduler="binding", seed=3,
             n_jobs=N_JOBS, scheduler_weights=(0.5, 0.3, 0.2),
             scale_out_bypass_util=0.8, scale_in_util_ceiling=0.6),
    CellSpec(scenario="diurnal", scheduler="k8s-default", autoscaler="binding",
             rescheduler="void", seed=3, n_jobs=N_JOBS,
             template_name="m2.medium"),
    CellSpec(scenario="heavy-tail", scheduler="best-fit",
             autoscaler="non-binding", rescheduler="non-binding", seed=3,
             n_jobs=N_JOBS, max_pod_age_s=30.0, provisioning_interval_s=20.0),
    CellSpec(scenario="flash-crowd", scheduler="best-fit",
             autoscaler="binding", rescheduler="void", seed=3, n_jobs=N_JOBS,
             template_name="m2.tiny"),   # infeasible: exercises short-circuit
]


def test_same_seed_same_rows():
    a = run_cells(CELLS, workers=1)
    b = run_cells(CELLS, workers=1)
    for ra, rb in zip(a, b):
        ra.pop("wall_s"), rb.pop("wall_s")
        assert ra == rb     # bit-identical floats, not approx


def test_parallel_bit_identical_to_serial_and_stable_order():
    serial = run_cells(CELLS, workers=1)
    parallel = run_cells(CELLS, workers=2)
    assert [r["label"] for r in parallel] == [c.label for c in CELLS]
    for rs, rp in zip(serial, parallel):
        rs.pop("wall_s"), rp.pop("wall_s")   # the only nondeterministic key
        assert rs == rp     # == on raw floats: bit-identical or bust


def test_infeasible_cell_short_circuits():
    [row] = run_cells([CELLS[4]], workers=1)
    assert row["infeasible"] is True
    assert row["completed"] is False
    assert row["cost"] == 0 and row["wall_s"] == 0.0


def test_failing_cell_raises_cell_error_naming_it():
    bad = CellSpec(scenario="no-such-scenario", seed=0, n_jobs=N_JOBS)
    with pytest.raises(CellError, match="no-such-scenario"):
        run_cells([bad], workers=1)
    with pytest.raises(CellError, match="no-such-scenario"):
        run_cells([bad, CELLS[0]], workers=2)


def test_worker_crash_surfaces_error_not_hang(monkeypatch):
    crash = CELLS[0]
    monkeypatch.setenv(_CRASH_ENV, crash.label)
    with pytest.raises(CellError, match=crash.scenario):
        run_cells([crash] + CELLS[1:3], workers=2)


def test_pool_workers_are_spawned_and_never_import_jax(tmp_path,
                                                      monkeypatch):
    """A stand-in ``jax`` package first on the workers' import path marks
    and refuses any import of JAX in a worker, and the pool must start
    its workers by spawning (a forked worker would inherit a parent that
    has already loaded JAX)."""
    marker = tmp_path / "jax_imported"
    fake = tmp_path / "path" / "jax"
    fake.mkdir(parents=True)
    (fake / "__init__.py").write_text(
        f"open({str(marker)!r}, 'w').close()\n"
        "raise ImportError('a cell worker imported jax')\n")
    monkeypatch.syspath_prepend(str(tmp_path / "path"))
    methods = []

    class RecordingPool(runner.ProcessPoolExecutor):
        def __init__(self, *args, mp_context=None, **kwargs):
            methods.append(mp_context.get_start_method())
            super().__init__(*args, mp_context=mp_context, **kwargs)

    monkeypatch.setattr(runner, "ProcessPoolExecutor", RecordingPool)
    rows = run_cells(CELLS[:3], workers=2)
    assert [r["label"] for r in rows] == [c.label for c in CELLS[:3]]
    assert methods == ["spawn"]
    assert not marker.exists()


def test_search_same_seed_identical_front_serial_vs_parallel():
    space = default_space()
    kwargs = dict(generations=1, pop_size=4, seed=11, n_jobs=N_JOBS)
    a = run_search(space, ("diurnal", "heavy-tail"), workers=1, **kwargs)
    b = run_search(space, ("diurnal", "heavy-tail"), workers=1, **kwargs)
    c = run_search(space, ("diurnal", "heavy-tail"), workers=2, **kwargs)
    for other in (b, c):
        assert [i.vector for i in a.front] == [i.vector for i in other.front]
        assert ([i.objectives for i in a.front]
                == [i.objectives for i in other.front])   # bit-identical
        assert a.history == other.history
    # Fronts are genuinely non-dominated and vector-sorted (stable order).
    vecs = [i.vector for i in a.front]
    assert vecs == sorted(vecs)
