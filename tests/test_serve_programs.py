"""``serve()`` keeps its compiled programs across calls: a repeated call on
the same mesh builds, lowers and compiles nothing, yet makes its weights,
prefills and decodes as before and serves the tokens a fresh build serves;
a new shape on the same mesh reuses the init program; two threads on one
key build each program once; and a build on one key never waits for
another key's."""

import pathlib
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

ARCH = "qwen2.5-3b"
BATCH, PROMPT, OTHER_PROMPT = 2, 8, 16
PROGRAMS = ["init", "prefill", "decode"]


class _Span:
    """A span that records its name and stats."""

    def __init__(self, log, name, stats):
        self.name, self.stats = name, dict(stats)
        log.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        self.stats.update(stats)


class _Call:
    def __init__(self, tokens, spans, compiled):
        self.tokens, self.spans, self.compiled = tokens, spans, compiled

    def named(self, name):
        return [s for s in self.spans if s.name == name]

    def lowered(self):
        return [s.stats["program"] for s in self.named("repro.serve.lower")]

    def cached(self):
        (job,) = self.named("repro.serve")
        return job.stats["cached"]


@pytest.fixture(scope="module")
def calls():
    """Every call serves on a new ``Mesh`` over the first device, as
    ``run_live`` makes one per instance; each call's spans and jax's
    trace, lowering and compile durations over it are kept."""
    import jax

    import repro.launch.serve as serve_mod
    from bench.lib.counters import Counters
    from repro.launch.mesh import make_mesh

    counters = Counters(jax)
    log: list = []

    def serve(seed, gen, prompt=PROMPT):
        mesh = make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
        return serve_mod.serve(ARCH, batch=BATCH, prompt_len=prompt, gen=gen,
                               smoke=True, mesh=mesh, seed=seed,
                               log_fn=lambda *_: None)["tokens"]

    def call(seed, gen, prompt=PROMPT):
        log.clear()
        before = counters.snapshot()
        tokens = serve(seed, gen, prompt)
        return _Call(tokens, list(log), counters.snapshot() - before)

    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serve_mod, "span",
                   lambda name, **stats: _Span(log, name, stats))
        serve_mod.clear_programs()
        out["first"] = call(1, 4)
        out["repeat"] = call(2, 6)
        out["other_shape"] = call(3, 4, OTHER_PROMPT)
        serve_mod.clear_programs()
        out["repeat_fresh"] = call(2, 6)
        serve_mod.clear_programs()
        out["other_shape_fresh"] = call(3, 4, OTHER_PROMPT)

        serve_mod.clear_programs()
        log.clear()
        start = threading.Barrier(2)
        threaded = {}

        def in_thread(seed, gen):
            start.wait()
            threaded[seed] = serve(seed, gen)

        threads = [threading.Thread(target=in_thread, args=args)
                   for args in ((1, 4), (2, 6))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        out["threads"] = (threaded, list(log))
        serve_mod.clear_programs()
    return out


def test_first_call_builds_every_program(calls):
    first = calls["first"]
    assert len(first.named("repro.serve.build")) == 1
    assert first.lowered() == PROGRAMS
    assert [s.stats["program"] for s in first.named(
        "repro.serve.compile")] == PROGRAMS
    assert first.cached() == ""
    assert first.compiled.lower_s > 0 and first.compiled.compile_s > 0


def test_repeat_call_builds_nothing(calls):
    """Same model, mesh and shape, another seed and ``gen``: every program
    comes from the store, and jax lowers and compiles nothing."""
    repeat = calls["repeat"]
    assert repeat.cached() == "init prefill decode"
    for stage in ("build", "lower", "compile"):
        assert repeat.named(f"repro.serve.{stage}") == []
    assert repeat.compiled.trace_s == 0
    assert repeat.compiled.lower_s == 0
    assert repeat.compiled.compile_s == 0
    # every job still makes its weights, prefills and decodes
    assert len(repeat.named("repro.serve.init")) == 1
    assert len(repeat.named("repro.serve.prefill")) == 1
    assert len(repeat.named("repro.serve.decode_step")) == 6 - 1


def test_repeat_call_serves_the_tokens_of_a_fresh_build(calls):
    hit, fresh = calls["repeat"], calls["repeat_fresh"]
    assert fresh.cached() == "" and fresh.lowered() == PROGRAMS
    assert hit.tokens.shape == (BATCH, 6)
    np.testing.assert_array_equal(hit.tokens, fresh.tokens)
    assert not np.array_equal(hit.tokens[:, :4], calls["first"].tokens)


def test_new_shape_reuses_the_init_program(calls):
    """The weights' program depends on the model and the mesh alone."""
    other = calls["other_shape"]
    assert other.cached() == "init"
    assert other.lowered() == ["prefill", "decode"]
    assert len(other.named("repro.serve.build")) == 1
    fresh = calls["other_shape_fresh"]
    assert fresh.lowered() == PROGRAMS
    np.testing.assert_array_equal(other.tokens, fresh.tokens)


def test_two_threads_on_one_key_build_each_program_once(calls):
    threaded, spans = calls["threads"]
    np.testing.assert_array_equal(threaded[1], calls["first"].tokens)
    np.testing.assert_array_equal(threaded[2], calls["repeat_fresh"].tokens)
    lowered = sorted(s.stats["program"] for s in spans
                     if s.name == "repro.serve.lower")
    assert lowered == sorted(PROGRAMS)
    # what one thread built, the other took from the store
    jobs = [s for s in spans if s.name == "repro.serve"]
    assert len(jobs) == 2
    kept = sorted(p for job in jobs for p in job.stats["cached"].split())
    assert kept == sorted(PROGRAMS)


def test_same_devices_and_layout_give_the_same_mesh_key():
    import jax

    from repro.launch.mesh import make_mesh
    from repro.launch.serve import _mesh_key

    one = jax.devices()[:1]
    a = make_mesh((1, 1), ("data", "model"), devices=one)
    b = make_mesh((1, 1), ("data", "model"), devices=one)
    assert _mesh_key(a) == _mesh_key(b)
    assert _mesh_key(a) != _mesh_key(
        make_mesh((1, 1), ("model", "data"), devices=one))
    assert _mesh_key(a) != _mesh_key(
        make_mesh((1,), ("data",), devices=one))


def test_a_build_does_not_hold_up_other_keys():
    """While one key's build runs, another key builds and returns, and a
    second caller of the running key waits for its one program."""
    from repro.launch.serve import _Programs

    store = _Programs()
    started, release = threading.Event(), threading.Event()
    builds = []

    def slow():
        builds.append("slow")
        started.set()
        assert release.wait(30)
        return object()

    got = {}

    def get(name):
        got[name] = store.get("slow", slow)

    first = threading.Thread(target=get, args=("first",))
    first.start()
    assert started.wait(30)
    second = threading.Thread(target=get, args=("second",))
    second.start()
    other = object()
    assert store.get("other", lambda: other) == (other, False)
    assert store.get("other", lambda: object()) == (other, True)
    assert first.is_alive() and second.is_alive()
    release.set()
    first.join(30)
    second.join(30)
    assert builds == ["slow"]
    assert got["first"][0] is got["second"][0]
    assert sorted(kept for _, kept in got.values()) == [False, True]


def test_a_failed_build_is_not_kept():
    from repro.launch.serve import _Programs

    store = _Programs()

    def fail():
        raise RuntimeError("no program")

    with pytest.raises(RuntimeError):
        store.get("key", fail)
    program = object()
    assert store.get("key", lambda: program) == (program, False)
