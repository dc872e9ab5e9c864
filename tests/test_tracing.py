"""The program's own spans, scopes and counters (``repro.utils.tracing``).

Each fit below runs once, under ``jax.profiler``, with the engines' jitted
round program kept aside: its compiled HLO must name the round phases in
its op metadata (``gal.<phase>``, each operation under at most one), the
host trace must nest the fit's spans on one thread under one fit id, and
the fit's top-level span must record its rounds and one round-program
build.
"""
from __future__ import annotations

import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest

from repro.utils import tracing

ROUNDS = 2
TABULAR_PHASES = {"residual", "local_fit", "weight_fit", "combine", "eta"}
SPANS = {"gal.plan", "gal.stage", "gal.launch", "gal.sync", "gal.finalize"}


class Recorded(NamedTuple):
    top: str                # the fit's top-level span
    module: str             # its round program's XLA module
    phases: set             # the scopes its round program must name
    programs: list          # (jitted, args) of each jax.jit called
    trace: object           # jax.profiler.ProfileData


def _tabular(engine, **config):
    from repro.core import gal
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    from repro.models import zoo
    key = jax.random.PRNGKey(0)
    xs = [jax.random.normal(jax.random.fold_in(key, i), (128, 3))
          for i in range(4)]
    y = jnp.sum(jnp.concatenate(xs, axis=1), axis=1, keepdims=True)

    def fit():
        orgs = make_orgs(xs, zoo.Linear())
        res = gal.fit(key, orgs, y, get_loss("mse"),
                      gal.GALConfig(rounds=ROUNDS, engine=engine, **config))
        assert res.engine == engine
        return res.stacked_params
    return fit


def _lm():
    from repro.configs import get_arch
    from repro.core import gal_lm
    cfg = get_arch("llama3-8b", smoke=True)
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    labels = jnp.roll(tokens, -1, axis=1)
    orgs = [gal_lm.LMOrganization(i, cfg, lambda t, i=i: (t + i) % cfg.vocab)
            for i in range(2)]
    for i, org in enumerate(orgs):
        org.init(jax.random.fold_in(key, i), lr=1e-3)

    def fit():
        res = gal_lm.fit_lm(key, orgs, tokens, labels, rounds=ROUNDS,
                            local_steps=2)
        return res.resume_state["f"]
    return fit


CASES = {
    "scan": ("gal.fit", "jit_gal_rounds", TABULAR_PHASES,
             lambda: _tabular("scan")),
    # the compressed wire puts operations under gal.broadcast
    "grouped": ("gal.fit", "jit_gal_rounds", TABULAR_PHASES | {"broadcast"},
                lambda: _tabular("grouped", residual_dtype="bf16")),
    "lm": ("gal.fit_lm", "jit_gal_lm_rounds", TABULAR_PHASES, _lm),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def recorded(request, tmp_path_factory):
    from repro.core import engine
    top, module, phases, make = CASES[request.param]
    fit = make()
    # a program built before would be reused, and the spy would see no build
    engine.clear_round_programs()
    programs = []
    real_jit = jax.jit

    def spy(fn, *args, **kwargs):
        jitted = real_jit(fn, *args, **kwargs)

        def call(*a):
            programs.append((jitted, a))
            return jitted(*a)
        return call

    out = tmp_path_factory.mktemp(request.param)
    jax.jit = spy
    try:
        jax.profiler.start_trace(str(out))
        try:
            jax.block_until_ready(fit())
        finally:
            jax.profiler.stop_trace()
    finally:
        jax.jit = real_jit
    path = next(out.rglob("*.xplane.pb"))
    return Recorded(top, module, phases, programs,
                    jax.profiler.ProfileData.from_file(str(path)))


def _spans(recorded):
    """The program's spans in the trace: (plane, thread, name, start, end,
    arguments)."""
    return [(plane.name, line.name, e.name, e.start_ns,
             e.start_ns + e.duration_ns, dict(e.stats))
            for plane in recorded.trace.planes for line in plane.lines
            for e in line.events if e.name.startswith("gal.")]


def test_round_program_names_its_phases(recorded):
    (jitted, args), = recorded.programs
    hlo = jitted.lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    scopes = [re.findall(r"(?:^|/)gal\.(\w+)", n) for n in names]
    assert {s[0] for s in scopes if s} == recorded.phases
    # each operation lies under one phase at most
    assert max(len(s) for s in scopes) == 1


def test_round_program_is_named(recorded):
    """The round program's XLA module carries the program's name, which
    also keys its compile apart from builds of an unnamed ``run``."""
    (jitted, args), = recorded.programs
    text = jitted.lower(*args).as_text()
    assert re.search(rf"module @{recorded.module}\b", text)


def test_fit_spans_nest_on_one_thread_with_one_fit_id(recorded):
    found = _spans(recorded)
    tops = [f for f in found if f[2] == recorded.top]
    assert len(tops) == 1
    where, thread, _, a, b, stats = tops[0]
    fit_id = stats["fit"]
    inner = [f for f in found if f is not tops[0]]
    assert {f[2] for f in inner} == SPANS
    for f in inner:
        assert f[:2] == (where, thread) and a <= f[3] <= f[4] <= b, f
        assert f[5] == {"fit": fit_id}, f
    sync = next(f for f in inner if f[2] == "gal.sync")
    assert any(f[2] == "gal.finalize" and f[3] <= sync[3] <= sync[4] <= f[4]
               for f in inner)


def test_counters_move_by_one_fit(recorded):
    # as it closes, the fit's span records its rounds and one build of
    # the round program
    top, = [f for f in _spans(recorded) if f[2] == recorded.top]
    assert top[5] == {"fit": top[5]["fit"], "rounds": ROUNDS,
                      "round_traces": 1}
    assert len(recorded.programs) == 1


def test_counters_and_fit_numbers(tmp_path):
    """Each fit's span records its own tally under a number of its own, a
    nested fit's apart from its caller's; a count outside a fit is
    dropped."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        tracing.count("rounds", 5)
        with tracing.fit_span("fit") as outer:
            tracing.count("rounds", 2)
            with tracing.fit_span("fit") as inner:
                tracing.count("rounds")
                tracing.count("round_traces")
            with tracing.span("plan"):
                pass
        with tracing.fit_span("fit") as after:
            pass
    finally:
        jax.profiler.stop_trace()
    trace = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    found = sorted(((e.name, dict(e.stats)) for plane in trace.planes
                    for line in plane.lines for e in line.events
                    if e.name.startswith("gal.")),
                   key=lambda f: (f[0], f[1]["fit"]))
    assert outer < inner < after
    assert found == [
        ("gal.fit", {"fit": outer, "rounds": 2, "round_traces": 0}),
        ("gal.fit", {"fit": inner, "rounds": 1, "round_traces": 1}),
        ("gal.fit", {"fit": after, "rounds": 0, "round_traces": 0}),
        ("gal.plan", {"fit": outer})]
