"""Device time by the program's named scopes, on a small synthetic trace
(the op events, program executions and instruction tables a TPU trace
carries), and the protobuf reader on a real trace recorded on the CPU."""
import glob
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from benchlib import harness as H
from benchlib import scopes as S
from benchlib.trace import TraceData, reduce

MS = 1_000_000  # ns
TABLES = {
    "jit_step(7)": {
        "fusion.1": "jit(step)/paged.materialize/gather",
        "while.2": "jit(step)/while",
        "fusion.3": "jit(step)/while/body/closed_call/attention/dot_general",
        "fusion.4": "jit(step)/while/body/transpose(jvp(mod.router))/sort",
        "copy.5": "",
        "fusion.6": "jit(step)/paged.writeback/scatter",
    },
    "jit_chunk(8)": {"fusion.1": "jit(chunk)/attention/dot_general"},
}


def op(name, s, e):
    return (f"%{name} = bf16[2]{{0}} fusion(bf16[2]{{0}} %p), kind=kLoop", s * MS, e * MS)


def trace(materialize=True):
    """Two decode steps in the window [0, 100) ms, a third past it, a chunk,
    and ops outside every program."""
    first = "fusion.1" if materialize else "copy.5"
    ops = [op(first, 0, 4), op("while.2", 4, 14), op("fusion.3", 5, 9), op("fusion.4", 9, 11),
           op("copy.5", 14, 16), op("fusion.6", 16, 18),
           op("fusion.1", 26, 27),  # between programs
           op(first, 30, 34), op("while.2", 34, 44), op("fusion.3", 35, 39),
           op("fusion.6", 44, 46),
           op("fusion.1", 60, 70),  # inside the chunk program
           op("fusion.1", 100, 104)]  # a step past the window
    mods = [("jit_step(7)", 0, 18), ("jit_step(7)", 30, 46), ("jit_chunk(8)", 60, 70),
            ("jit_step(7)", 100, 118)]
    spans = [("bench.window", 0, 100 * MS)]
    for k, (s, e) in enumerate([(0, 28), (28, 58)]):
        spans += [("engine.step", s * MS, e * MS), ("serve.decode", s * MS, (s + 2) * MS),
                  ("serve.logits_to_host", (s + 18) * MS, (s + 22) * MS),
                  ("serve.sample", (s + 22) * MS, (s + 25) * MS)]
    return TraceData([ops], [[(n, s * MS, e * MS) for n, s, e in mods]], spans)


def program(td, prefix="jit_step("):
    red = reduce(td)
    return S.ProgramScopes(td, TABLES, prefix, red.lo, red.hi)


def test_time_goes_to_each_ops_scope_path_by_its_self_time():
    ps = program(trace())
    assert ps.executions == 2
    split = {k: pytest.approx(v) for k, v in ps.split().items()}
    # the loop op keeps only its own time (10 - 4 - 2 ms, then 10 - 4 ms)
    assert split == {"paged.materialize": 0.008, "attention": 0.008, "mod.router": 0.002,
                     "paged.writeback": 0.004, S.UNSCOPED: 0.004 + 0.006 + 0.002}
    assert ps.marked == {"paged.materialize", "attention", "mod.router", "paged.writeback"}
    assert dict(ps.unscoped) == pytest.approx({"while.2 [jit(step)/while]": 0.010,
                                               "copy.5 []": 0.002})


def test_ops_outside_the_programs_executions_or_the_window_are_left_out():
    ps = program(trace())
    total = sum(ps.split().values())
    assert total == pytest.approx(0.034)  # 18 + 16 ms: no op of 26-27, 60-70 or 100+ ms
    chunk = program(trace(), "jit_chunk(")
    assert chunk.executions == 1 and chunk.ms_per_execution(["attention"]) == pytest.approx(10.0)


def test_time_is_per_execution_of_the_program():
    ps = program(trace())
    assert ps.ms_per_execution(["paged.materialize", "paged.writeback"]) == pytest.approx(6.0)
    assert ps.ms_per_execution(["attention"]) == pytest.approx(4.0)
    assert ps.ms_per_execution(["mod.router", "mod.dispatch"]) == pytest.approx(1.0)


def test_a_program_without_the_scope_reads_none():
    tables = {"jit_step(7)": {k: "jit(step)/dot_general" for k in TABLES["jit_step(7)"]}}
    red = reduce(trace())
    old = S.ProgramScopes(trace(), tables, "jit_step(", red.lo, red.hi)
    assert old.ms_per_execution(["paged.materialize"]) is None
    assert program(trace()).ms_per_execution(["mlp"]) is None


def test_scope_paths_lose_their_transformation_wrappers():
    assert S.scope_path("jit(step_fn)/transpose(jvp(attention))/dot_general") == (
        "step_fn", "attention", "dot_general")
    assert S.instruction("%fusion.12 = bf16[2]{0} fusion(bf16[2]{0} %p)") == "fusion.12"
    assert S.instruction("copy.3") == "copy.3"


def traced_run(td, monkeypatch, window=None, tables=TABLES):
    """A traced run whose newest trace file gives ``window`` (the run's own
    by default) and ``tables``."""
    red = reduce(td)
    win = window or (red.lo, red.hi)
    S._CACHE.clear()
    monkeypatch.setattr(S, "newest_xplane", lambda root: "trace.xplane.pb")
    monkeypatch.setattr(S.os.path, "getmtime", lambda path: 1.0)
    monkeypatch.setattr(S, "read_xplane", lambda path, prefix: (win, tables))
    return SimpleNamespace(td=td, red=red)


def test_a_trace_of_another_window_reads_none(monkeypatch):
    run = traced_run(trace(), monkeypatch, window=(0.0, 90 * MS))
    assert S.of_run(run, "jit_step(") is None
    assert H.reader("paged_copy_ms_per_step.decode")(run) is None


def test_the_decode_readers(monkeypatch):
    run = traced_run(trace(), monkeypatch)
    assert H.reader("paged_copy_ms_per_step.decode")(run) == pytest.approx(6.0)
    # idle device inside logits_to_host (18-22 ms: all idle) and sample
    # (22-25 ms: all idle), per serve.decode span
    assert H.reader("sample_host_ms_per_step.decode")(run) == pytest.approx(7.0)
    td = trace()
    td.host_spans = [s for s in td.host_spans if not s[0].startswith("serve.")]
    assert H.reader("sample_host_ms_per_step.decode")(traced_run(td, monkeypatch)) is None
    assert H.reader("paged_copy_ms_per_step.decode")(
        traced_run(trace(materialize=False), monkeypatch,
                   tables={"jit_step(7)": {"copy.5": ""}})) is None


def test_the_train_readers(monkeypatch):
    td = trace()
    td.modules = [[("jit_step_fn(3)" if n.startswith("jit_step(") else n, s, e)
                   for n, s, e in td.modules[0]]]
    run = traced_run(td, monkeypatch, tables={"jit_step_fn(3)": TABLES["jit_step(7)"]})
    assert H.reader("attention_ms_per_step.train")(run) == pytest.approx(4.0)
    assert H.reader("mod_routing_ms_per_step.train")(run) == pytest.approx(1.0)


def test_the_protobuf_reader_on_a_recorded_trace(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("attention"):
            y = jnp.sin(x) @ x
        return y + 1

    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    win, tables = S.read_xplane(path, "jit_f(")
    host = [e for p in jax.profiler.ProfileData.from_file(path).planes if p.name == "/host:CPU"
            for ln in p.lines for e in ln.events if e.name == "bench.window"]
    assert win == pytest.approx((host[0].start_ns, host[0].start_ns + host[0].duration_ns))
    (table,) = tables.values()
    marked = {name for name, o in table.items() if "attention" in S.scope_path(o)}
    assert marked and all(table[n].startswith("jit(f)/attention/") for n in marked)
    assert not any("attention" in S.scope_path(o) for n, o in table.items() if n not in marked)
