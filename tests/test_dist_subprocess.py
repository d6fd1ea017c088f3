"""Distributed training with REAL pserver/trainer subprocesses — the
reference's cluster-simulation discipline (reference:
tests/unittests/test_dist_base.py:213 start_pserver + run_trainer in
separate processes), closing the thread-based test's GIL blind spot."""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

import paddle_tpu.fluid as fluid


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_subprocess_cluster_matches_local():
    n_steps = 6
    eps = ["127.0.0.1:%d" % _free_port(), "127.0.0.1:%d" % _free_port()]
    env_base = dict(
        os.environ,
        PADDLE_PSERVER_EPS=",".join(eps),
        PADDLE_TRAINERS="2",
        PADDLE_STEPS=str(n_steps),
        JAX_PLATFORMS="cpu",
    )
    worker = os.path.join(os.path.dirname(__file__), "dist_worker.py")

    pservers = []
    for ep in eps:
        env = dict(env_base, PADDLE_ROLE="PSERVER", PADDLE_CURRENT_EP=ep)
        pservers.append(subprocess.Popen(
            [sys.executable, worker], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    # wait for both servers to bind
    for p in pservers:
        line = p.stdout.readline().strip()
        assert line == "READY", (line, p.stderr.read())

    trainers = []
    for tid in range(2):
        env = dict(env_base, PADDLE_ROLE="TRAINER",
                   PADDLE_TRAINER_ID=str(tid))
        trainers.append(subprocess.Popen(
            [sys.executable, worker], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))

    results = []
    for p in trainers:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, err[-2000:]
        for line in out.splitlines():
            if line.startswith("LOSSES "):
                results.append(json.loads(line[len("LOSSES "):]))
    assert len(results) == 2, results
    for p in pservers:
        try:
            p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()

    # local oracle: same model, same init, full batches
    sys.path.insert(0, os.path.dirname(__file__))
    from dist_worker import batches, build

    main, startup, loss, init = build()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for k, v in init.items():
            scope.set(k, v)
        local_losses = []
        for b in batches(n_steps, 32):
            (l,) = exe.run(main, feed=b, fetch_list=[loss], scope=scope)
            local_losses.append(float(np.asarray(l)))

    dist_losses = [(a + b) / 2 for a, b in zip(*results)]
    np.testing.assert_allclose(dist_losses, local_losses, rtol=1e-4,
                               atol=1e-5)
    assert dist_losses[-1] < dist_losses[0]


def _run_cluster(mode, n_steps=6, n_trainers=2):
    """Spawn a real pserver/trainer process cluster in the given mode and
    return each trainer's per-step losses."""
    eps = ["127.0.0.1:%d" % _free_port(), "127.0.0.1:%d" % _free_port()]
    env_base = dict(
        os.environ,
        PADDLE_PSERVER_EPS=",".join(eps),
        PADDLE_TRAINERS=str(n_trainers),
        PADDLE_STEPS=str(n_steps),
        PADDLE_DIST_MODE=mode,
        JAX_PLATFORMS="cpu",
    )
    worker = os.path.join(os.path.dirname(__file__), "dist_worker.py")
    pservers = []
    for ep in eps:
        env = dict(env_base, PADDLE_ROLE="PSERVER", PADDLE_CURRENT_EP=ep)
        pservers.append(subprocess.Popen(
            [sys.executable, worker], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    for p in pservers:
        line = p.stdout.readline().strip()
        assert line == "READY", (line, p.stderr.read())
    trainers = []
    for tid in range(n_trainers):
        env = dict(env_base, PADDLE_ROLE="TRAINER",
                   PADDLE_TRAINER_ID=str(tid))
        trainers.append(subprocess.Popen(
            [sys.executable, worker], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    results = []
    for p in trainers:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, err[-2000:]
        for line in out.splitlines():
            if line.startswith("LOSSES "):
                results.append(json.loads(line[len("LOSSES "):]))
    for p in pservers:
        try:
            p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
    assert len(results) == n_trainers, results
    return results


def test_subprocess_async_cluster_converges():
    """Async (no-barrier) pserver loop under REAL process isolation —
    the GIL-threaded in-process test can't catch races in the
    apply-as-grads-arrive path (reference: listen_and_serv_op.cc
    RunAsyncLoop; test discipline of test_dist_base.py:213)."""
    results = _run_cluster("async", n_steps=100)
    for losses in results:
        # every step draws a fresh batch of 16 at a loss near ln 4, so one
        # step against another is a coin the race order flips (10 steps:
        # 1 failure in 6 under load, PR 32); ten steps' mean is not
        assert np.mean(losses[-10:]) < np.mean(losses[:10]), losses
        assert all(np.isfinite(l) for l in losses), losses


def test_subprocess_lookup_table_matches_local():
    """Distributed lookup table (prefetch + sparse pushback + shard-only
    memory) as a real subprocess cluster, checked against a local oracle
    (reference: parameter_prefetch.cc under test_dist_base discipline)."""
    n_steps = 6
    results = _run_cluster("lookup", n_steps=n_steps)

    sys.path.insert(0, os.path.dirname(__file__))
    import importlib

    dw = importlib.import_module("dist_worker")
    # local oracle: same model without distribution, full batches
    import paddle_tpu.fluid as fl
    from paddle_tpu.framework import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        ids = fl.layers.data(name="ids", shape=[dw.FIELDS], dtype="int64")
        y = fl.layers.data(name="y", shape=[1], dtype="int64")
        emb = fl.layers.embedding(
            ids, size=[dw.VOCAB, dw.DIM], is_sparse=True,
            param_attr=fl.ParamAttr(name="emb_w"))
        pooled = fl.layers.reduce_sum(emb, dim=1)
        pred = fl.layers.fc(input=pooled, size=4,
                            param_attr=fl.ParamAttr(name="fc_w"),
                            bias_attr=False)
        loss = fl.layers.mean(fl.layers.softmax_with_cross_entropy(
            logits=pred, label=y))
        fl.optimizer.SGD(learning_rate=0.2).minimize(loss)
    exe = fl.Executor()
    scope = fl.Scope()
    with fl.scope_guard(scope):
        exe.run(startup)
        scope.set("emb_w", np.linspace(
            -0.5, 0.5, dw.VOCAB * dw.DIM).astype(np.float32).reshape(
                dw.VOCAB, dw.DIM))
        scope.set("fc_w", np.linspace(
            0.2, -0.2, dw.DIM * 4).astype(np.float32).reshape(dw.DIM, 4))
        local_losses = []
        for b in dw.lookup_batches(n_steps, 32):
            (l,) = exe.run(main, feed=b, fetch_list=[loss], scope=scope)
            local_losses.append(float(np.asarray(l)))

    dist_losses = [(a + b) / 2 for a, b in zip(*results)]
    np.testing.assert_allclose(dist_losses, local_losses, rtol=1e-4,
                               atol=1e-5)
