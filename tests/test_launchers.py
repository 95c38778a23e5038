"""Launcher entry points (train/serve) exercised at tiny scale."""
import jax
import numpy as np
import pytest

from repro.launch.train import train


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    # serve.main() points JAX's persistent compile cache at the checkout for
    # the rest of its process; in a test worker that would outlive the test
    monkeypatch.setattr("repro.launch.serve.enable_compile_cache",
                        lambda: None)


def test_train_launcher_reduced_arch():
    losses = train("olmo-1b", reduced=True, steps=12, batch_size=2, seq=32,
                   lr=2e-3, vocab=128, log_every=100)
    assert len(losses) == 12
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_train_launcher_moe_arch():
    losses = train("olmoe-1b-7b", reduced=True, steps=6, batch_size=2,
                   seq=16, lr=2e-3, vocab=64, log_every=100)
    assert np.isfinite(losses).all()


def test_serve_launcher_main(monkeypatch, capsys):
    import sys
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "olmo-1b", "--reduced", "--batch", "2",
        "--prompt-len", "8", "--new-tokens", "4", "--vocab", "128"])
    serve.main()
    out = capsys.readouterr().out
    assert "tokens" in out


def test_serve_launcher_gam(monkeypatch, capsys):
    import sys
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "tinyllama-1.1b", "--reduced", "--batch", "2",
        "--prompt-len", "8", "--new-tokens", "4", "--vocab", "128", "--gam"])
    serve.main()
    out = capsys.readouterr().out
    assert "vocab rows scored/step" in out


def test_serve_help_pins_the_flag_surface(monkeypatch, capsys):
    """``--help`` is the serving CLI's public contract: every documented
    flag group is present (including the traffic-realism trio) and stale
    references to retired names/formats can't creep back in."""
    import sys

    import pytest

    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", ["serve", "--help"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code == 0
    out = capsys.readouterr().out
    for flag in ("--service", "--items", "--shards", "--requests",
                 "--cache N", "--cache-ttl-s S", "--load-profile SPEC",
                 "--hosts N", "--replication R", "--snapshot PATH",
                 "--metrics-out PATH", "--trace-out PATH", "--learn",
                 "--queue-cap N", "--deadline-ms MS", "--inject-faults",
                 "--verify"):
        assert flag in out, f"--help lost {flag!r}"
    # the load harness help must point at its documentation
    assert "docs/load_testing.md" in out
    assert "zipf=1.1,curve=diurnal" in out
    # retired names / formats must not resurface in user-facing text
    for stale in ("GamService", "snapshot v3", "repro.retriever/v3"):
        assert stale not in out, f"stale reference {stale!r} in --help"


def test_serve_loop_survives_no_live_replica(capsys):
    """The serve loop's guarded query converts an unservable round into a
    typed, counted shed and keeps serving — marking the host back up makes
    the very next round answer again (no restart, no stuck state)."""
    from conftest import unit_factors
    from repro.launch.serve import _guarded_query
    from repro.retriever import RetrieverSpec, open_retriever

    items = unit_factors(200, 16, 0)
    users = unit_factors(4, 16, 1)
    spec = RetrieverSpec(cfg=__import__("conftest").CFG,
                         backend="sharded-multihost", n_shards=2,
                         min_overlap=1, kappa=8, n_hosts=2, replication=1)
    svc = open_retriever(spec, items=items)
    want = _guarded_query(svc, users)
    assert want is not None

    svc.mark_down(0)                  # replication=1: slice 0 unservable
    assert _guarded_query(svc, users) is None
    assert _guarded_query(svc, users) is None
    snap = svc.metrics.snapshot()
    assert snap["shed_no_live_replica"] == 2 == snap["shed_total"]
    kinds = [e["kind"] for e in svc.events.tail(10)]
    assert "request_shed" in kinds

    svc.mark_up(0)                    # recovery is immediate and exact
    got = _guarded_query(svc, users)
    np.testing.assert_array_equal(got.ids, want.ids)
    np.testing.assert_array_equal(got.scores, want.scores)


def test_serve_launcher_service_qos_flags(monkeypatch, capsys):
    """End-to-end single-process service demo with QoS + chaos flags on:
    the stream finishes, and the QoS summary line reports typed sheds /
    degraded counts instead of crashing on injected delta errors."""
    import sys
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--service", "--items", "300", "--shards", "2",
        "--requests", "24", "--service-batch", "4", "--queue-cap", "16",
        "--deadline-ms", "200", "--inject-faults", "delta_error=1.0"])
    serve.main()
    out = capsys.readouterr().out
    assert "qos:" in out and "upsert faults=1" in out


def test_compile_cache_lands_in_the_checkout(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR unset the cache is the fixed
    <checkout>/.jax_cache; when it is set, JAX reads it and nothing else is
    configured."""
    from pathlib import Path

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = Path(__file__).resolve().parents[1]
        assert compile_cache.enable_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_cpu_worker_spawn_refuses_on_an_accelerator(monkeypatch):
    """--hosts N is a multi-process CPU demo: where JAX's backend is a TPU
    it refuses before spawning anything, instead of serving from the CPU."""
    import subprocess

    from repro.launch import procs

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "Popen", None)    # nothing may spawn
    with pytest.raises(RuntimeError, match="'tpu'"):
        procs.run_workers([["true"]])


def test_serve_hosts_refuses_on_an_accelerator(monkeypatch, capsys):
    import sys

    from repro.launch import serve

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sys, "argv", ["serve", "--service", "--hosts", "2"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code == 2
    assert "multi-process CPU demo" in capsys.readouterr().err
