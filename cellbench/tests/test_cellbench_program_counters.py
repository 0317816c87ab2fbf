"""The readers of the program's own counters: the server's queue wait and
the process-wide XLA compile count, each read inside the window, and each
silent (None, no error) on a program that keeps no such counter."""
import time
import types

import jax
import jax.numpy as jnp
import pytest

import _paths  # noqa: F401
import repro.retriever
from harness import spec


def _ctx(**kw):
    return types.SimpleNamespace(**kw)


def test_queue_wait_reads_the_servers_mean():
    read = spec.metric_reader("server.queue_wait_ms")
    assert read(_ctx(server_stats={"queue_wait_mean_ms": 101.5})) == 101.5
    assert read(_ctx(server_stats={"queue_wait_mean_ms": float("nan")})) \
        is None
    assert read(_ctx(server_stats={"n_requests": 3})) is None


def test_xla_compiles_counts_only_inside_the_window():
    read = spec.metric_reader("facade.xla_compiles_in_window")
    f = jax.jit(lambda x: x * 5.0 + 2.0)
    t0 = time.perf_counter()
    jax.block_until_ready(f(jnp.zeros(11)))           # compiles: in window
    t1 = time.perf_counter()
    jax.block_until_ready(f(jnp.zeros(11)))           # warmed
    jax.block_until_ready(f(jnp.zeros(13)))           # after the window
    rec = types.SimpleNamespace(t_start=t0, t_end=t1)
    assert read(_ctx(record=rec)) >= 1
    assert read(_ctx(record=types.SimpleNamespace(
        t_start=t1, t_end=time.perf_counter()))) >= 1
    later = time.perf_counter()
    assert read(_ctx(record=types.SimpleNamespace(
        t_start=later, t_end=later + 1.0))) == 0


def test_readers_are_silent_on_a_program_without_the_counters(monkeypatch):
    monkeypatch.delattr(repro.retriever, "xla_compile_count")
    rec = types.SimpleNamespace(t_start=0.0, t_end=1.0)
    assert spec.metric_reader("facade.xla_compiles_in_window")(
        _ctx(record=rec)) is None
    assert spec.metric_reader("server.queue_wait_ms")(
        _ctx(server_stats={"p99_ms": 1.0})) is None


@pytest.mark.parametrize("name", ["server.queue_wait_ms",
                                  "facade.xla_compiles_in_window"])
def test_program_counter_metrics_are_declared(name):
    m = {x["name"]: x for x in spec.load_benchmark()["per_layer"]}[name]
    assert m["source"] == "program_counter" and m["moves"] == "qps"
