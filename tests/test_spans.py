"""Spans on the profiler's clock and stable program names.

``repro.telemetry.timing``'s spans appear in a ``jax.profiler`` trace as
``repro.<name>`` with their metadata as stats; ``run_training`` marks each
write event with a step marker and names its programs by role; ``api.run``
records its phases, numbered by the call, on its ``RunRecord``; and
``launch/report.py`` prints the mean of each span.
"""
import types
import warnings

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import api
from repro.launch import report
from repro.launch import train as train_mod
from repro.sweep import grid as grid_mod
from repro.telemetry import (COMPILE_EVENT_NAMES, drain_timings,
                             numbered_run, run_number, span, step_span, timed)

HOST_PLANE = "/host:CPU"


def capture(tmp_path, fn):
    """Run ``fn`` under the profiler (annotations only: no Python
    function tracer); its result and the host events of the trace as
    ``(name, start_ns, duration_ns, stats)``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    with warnings.catch_warnings():  # jaxlib's stats type lacks __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        events = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                  for plane in data.planes if plane.name == HOST_PLANE
                  for line in plane.lines for e in line.events]
    return out, events


def named(events, name):
    return sorted((e for e in events if e[0] == name), key=lambda e: e[1])


def inside(inner, outer):
    return (outer[1] <= inner[1]
            and inner[1] + inner[2] <= outer[1] + outer[2])


# ------------------------------------------------------------ span API ----

def test_timed_records_and_annotates(tmp_path):
    drain_timings()

    def work():
        with timed("unit.block", run=3, width=4):
            pass

    _, events = capture(tmp_path, work)
    buffered = drain_timings()
    assert [e["name"] for e in buffered] == ["unit.block"]
    assert buffered[0]["run"] == 3 and buffered[0]["width"] == 4
    assert buffered[0]["ms"] >= 0
    (ev,) = named(events, "repro.unit.block")
    assert ev[3]["run"] == 3 and ev[3]["width"] == 4


def test_span_and_step_span_annotate_only(tmp_path):
    drain_timings()

    def work():
        for k in range(2):
            with step_span("unit.step", k, worker=1):
                with span("unit.inner", tag="x"):
                    pass

    _, events = capture(tmp_path, work)
    assert drain_timings() == []
    steps = named(events, "repro.unit.step")
    assert [e[3]["step_num"] for e in steps] == [0, 1]
    assert all(e[3]["worker"] == 1 for e in steps)
    inner = named(events, "repro.unit.inner")
    assert len(inner) == 2 and inner[0][3]["tag"] == "x"
    assert all(inside(i, s) for i, s in zip(inner, steps))


def test_run_numbers_are_scoped():
    assert run_number() == 0
    with numbered_run() as a:
        assert run_number() == a
        with numbered_run() as b:
            assert b == a + 1 and run_number() == b
        assert run_number() == a
    assert run_number() == 0


# ------------------------------------------------------------- trainer ----

def tiny_lm():
    return train_mod.PRESETS["25m"].replace(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=1, head_dim=16,
        d_ff=64, vocab=64, name="lm-spans")


def test_trainer_marks_every_write_event(tmp_path):
    steps, log_every = 5, 2
    log, events = capture(tmp_path, lambda: train_mod.run_training(
        tiny_lm(), steps=steps, batch=1, seq=16, n_workers=2,
        log_every=log_every))
    marks = named(events, "repro.train.event")
    assert [e[3]["step_num"] for e in marks] == list(range(steps))
    assert all({"worker", "tau"} <= set(e[3]) for e in marks)
    for inner in ("apply", "batch", "grad"):
        got = named(events, f"repro.train.{inner}")
        assert len(got) == steps
        assert all(inside(g, m) for g, m in zip(got, marks))
    logged = named(events, "repro.train.log")
    assert len(logged) == len(log) == 3  # events 0, 2 and the last
    assert len(named(events, "repro.train.init_grads")) == 1
    # names stay bare: metadata rides as stats, never in the name
    assert all("#" not in e[0] and "=" not in e[0]
               for e in events if e[0].startswith("repro."))
    # no memory counters on the CPU; the keys are left out, not zero
    assert all("bytes_in_use" not in r for r in log)


def test_trainer_programs_have_stable_names(monkeypatch):
    """Each program ``run_training`` jits lowers under its role's name."""
    modules = []

    def recording_jit(fun, **kw):
        jitted = jax.jit(fun, **kw)

        def call(*args):
            if not any(m[1] is jitted for m in modules):
                text = jitted.lower(*args).as_text()
                modules.append((text.split()[1].lstrip("@"), jitted))
            return jitted(*args)
        return call

    monkeypatch.setattr(train_mod, "jax", types.SimpleNamespace(
        **dict(vars(jax), jit=recording_jit)))
    train_mod.run_training(tiny_lm(), steps=2, batch=1, seq=16, n_workers=2,
                           log_every=1)
    assert sorted(m[0] for m in modules) == [
        "jit_heldout_loss", "jit_step_fn", "jit_train_grad"]


def test_tau_bar_program_name():
    T = jnp.ones((2, 3, 9), jnp.float32)
    text = grid_mod._tau_max_jit.lower(T).as_text()
    assert text.split()[1] == "@jit_measure_tau_bar"


# ------------------------------------------------------------- api.run ----

def declarative_spec(seeds=(0, 1), names=("uniform", "straggler"),
                     policies=("adaptive1", "fixed")):
    return api.ExperimentSpec(
        problem=api.ProblemSpec(kind="logreg",
                                params=dict(n_samples=120, dim=20, seed=0)),
        solver=api.SolverSpec(name="piag", horizon=4096),
        topology=api.TopologySpec(kind="standard", names=names,
                                  n_workers=(4,)),
        policies=api.PolicyGridSpec(names=policies, seeds=seeds),
        n_events=60)


def test_run_record_holds_its_phases(tmp_path):
    spec = declarative_spec()
    first, events = capture(tmp_path, lambda: api.run(spec))
    second = api.run(spec)
    timings = first.telemetry.timings
    names = [t["name"] for t in timings]
    for phase in ("api.resolve", "api.tau_bar", "api.dispatch",
                  "api.record", "sweep.service_times", "bucket_dispatch"):
        assert phase in names, names
    assert names[-1] == "api.record"
    n = timings[0]["run"]
    assert all(t["run"] == n for t in timings if t["name"].startswith("api."))
    assert {t["run"] for t in second.telemetry.timings
            if "run" in t} == {n + 1}
    assert first.telemetry.compile_ms == pytest.approx(
        sum(t["ms"] for t in timings if t["name"] in COMPILE_EVENT_NAMES))
    # the same phases on the profiler's clock, api.tau_bar inside resolve
    (resolve,) = named(events, "repro.api.resolve")
    (tau_bar,) = named(events, "repro.api.tau_bar")
    (dispatch,) = named(events, "repro.api.dispatch")
    (record,) = named(events, "repro.api.record")
    assert inside(tau_bar, resolve)
    assert resolve[1] + resolve[2] <= dispatch[1]
    assert dispatch[1] + dispatch[2] <= record[1]
    assert {e[3]["run"] for e in (resolve, tau_bar, dispatch, record)} == {n}
    assert drain_timings() == []


@pytest.mark.parametrize("cells,layout", [(1, "gathered"),
                                          (4, "grouped")])
def test_bucket_dispatch_carries_grad_layout(tmp_path, cells, layout):
    """Width 4: one cell keeps the gathered worker gradient, four take the
    grouped one; the bucket's span and its buffered event say which."""
    spec = (declarative_spec(seeds=(0,), names=("uniform",),
                             policies=("adaptive1",)) if cells == 1
            else declarative_spec(seeds=(0,)))
    res, events = capture(tmp_path, lambda: api.run(spec))
    assert len(res) == cells
    (buffered,) = [t for t in res.telemetry.timings
                   if t["name"] == "bucket_dispatch"]
    assert buffered["grad"] == layout and buffered["cells"] == cells
    (ev,) = named(events, "repro.bucket_dispatch")
    assert ev[3]["grad"] == layout and ev[3]["width"] == 4
    assert drain_timings() == []


def test_report_prints_mean_span_ms():
    recs = [{"timings": [{"name": "api.dispatch", "ms": 10.0, "run": 1},
                         {"name": "api.resolve", "ms": 2.0, "run": 1}]},
            {"timings": [{"name": "api.dispatch", "ms": 30.0, "run": 2}]}]
    lines = report.render_spans(recs)
    rows = {line.split()[0]: line.split()[1:] for line in lines[1:]}
    assert rows["api.dispatch"] == ["2", "20.000"]
    assert rows["api.resolve"] == ["1", "2.000"]
