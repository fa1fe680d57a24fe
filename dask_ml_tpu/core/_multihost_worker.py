"""Multi-host dryrun worker: one process of the SPMD group.

Run as ``python -m dask_ml_tpu.core._multihost_worker <pid> <nproc> <port>
[<local_devices>]``.  Every process executes the SAME program (JAX
multi-controller): bootstrap the group over localhost (Gloo collectives —
the ``gen_cluster`` analogue: real protocol stack, fake cluster), build the
global mesh, ingest per-host row blocks into one global ShardedRows, and
run the framework's two flagship SPMD programs across the process
boundary — an ADMM logistic solve and a fused Lloyd loop — asserting both
converge on the global data.

Used by ``__graft_entry__.dryrun_multihost`` and
``tests/test_multihost.py``.
"""

from __future__ import annotations

import os
import sys


def main(pid: int, nproc: int, port: str, local_devices: int = 4) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from dask_ml_tpu.core import distributed as dist

    dist.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
        local_device_count=local_devices,
    )
    assert jax.process_count() == nproc

    import numpy as np
    import jax.numpy as jnp

    from dask_ml_tpu.core.mesh import set_mesh
    from dask_ml_tpu.solvers import Logistic, admm

    mesh = dist.global_mesh()
    assert len(mesh.devices.flat) == nproc * local_devices
    set_mesh(mesh)

    # Per-host row block of one global dataset: process p holds rows
    # [p*block, (p+1)*block) — deterministic across the group.
    n_per, d = 400, 6
    rng = np.random.RandomState(0)
    w_true = rng.normal(size=d).astype(np.float32)
    rng_p = np.random.RandomState(100 + pid)
    Xl = rng_p.normal(size=(n_per, d)).astype(np.float32)
    yl = (Xl @ w_true > 0).astype(np.float32)

    Xs = dist.shard_rows_global(Xl, mesh)
    ys = dist.shard_rows_global(yl, mesh)
    assert Xs.n_samples == n_per * nproc

    # -- flagship 1: ADMM logistic across hosts (psums ride the process
    # boundary — DCN on a real fleet, Gloo here)
    beta = admm(Xs, ys, family=Logistic, lamduh=1e-4, max_iter=50)

    @jax.jit
    def accuracy(x, y, mask, b):
        pred = (x @ b > 0).astype(jnp.float32)
        return jnp.sum((pred == y) * mask) / jnp.sum(mask)

    acc = float(accuracy(Xs.data, ys.data, Xs.mask, beta))
    assert acc > 0.9, f"ADMM cross-host accuracy {acc}"

    # -- flagship 2: fused Lloyd loop on the same global mesh
    from dask_ml_tpu.cluster.k_means import _lloyd_loop
    from dask_ml_tpu.ops.scatter import scatter_strategy

    _scatter = scatter_strategy(2)  # resolved OUTSIDE the jit (static):
    # defaulting it would bake segsum in and drop the TPU onehot policy
    # a replicated operand must hold the SAME value on every process: the
    # centres come from the shared stream, not from this process's rows
    # (the anchored Lloyd reduce adds its sums to the centres it was given,
    # so centres that differed by process would never agree and the
    # processes would leave the loop at different iterations)
    centers0 = np.stack([w_true, w_true + 2.0]).astype(np.float32)
    centers, inertia, n_iter = _lloyd_loop(
        Xs.data, Xs.mask, jnp.asarray(centers0),
        jnp.float32(1e-4), jnp.int32(20), scatter=_scatter,
    )[:3]
    assert np.isfinite(float(inertia))

    # hierarchical mesh builds too (explicit DCN axis)
    hmesh = dist.global_mesh(hierarchical=True)
    assert hmesh.axis_names == (dist.DCN_AXIS, "data", "model")

    # -- flagship 6 (this round): cross-process PREEMPTION drill.  The
    # multi-controller contract (resilience/preemption.py): a watcher is
    # installed on EVERY process (the boundary flag check is itself a
    # tiny collective — a process without a watcher would skip it and
    # desynchronize the fleet), the signal lands on ONE process only
    # (process 0, via the programmatic trigger — a real SIGTERM hits one
    # host first the same way), and every process must stop at the SAME
    # iteration boundary with a final snapshot, then resume to
    # completion from it.
    import tempfile

    from dask_ml_tpu.linear_model import SGDRegressor
    from dask_ml_tpu.resilience import (
        FitCheckpoint,
        PreemptionWatcher,
        TrainingPreempted,
        fault_plan,
    )

    set_mesh(mesh)
    ckpt_path = os.path.join(
        tempfile.gettempdir(), f"dmlt_preempt_{port}_{pid}.pkl"
    )
    if os.path.exists(ckpt_path):
        os.unlink(ckpt_path)

    def make_sgd():
        # tol=None: a fixed 10-epoch schedule, so the stopping boundary
        # is deterministic and identical on every process
        return SGDRegressor(
            random_state=0, tol=None, max_iter=10, eta0=0.01,
            learning_rate="constant",
            fit_checkpoint=FitCheckpoint(ckpt_path, every_n_iters=2),
        )

    with PreemptionWatcher() as w:
        stopped_at = None
        try:
            if pid == 0:
                with fault_plan() as plan:
                    plan.on_call("step", w.trigger, at_call=2)
                    make_sgd().fit(Xs, ys)
            else:
                make_sgd().fit(Xs, ys)
        except TrainingPreempted as e:
            stopped_at = e.iteration
            assert e.checkpoint_path == ckpt_path, e.checkpoint_path
    # the flag collective must stop EVERY process (only pid 0 saw the
    # "signal"), and at the same boundary: the end of epoch 2
    assert stopped_at == 2, (
        f"proc {pid}: expected a fleet-wide stop at epoch 2, "
        f"got {stopped_at}"
    )
    assert os.path.exists(ckpt_path), "no final snapshot at preemption"
    sgd = make_sgd().fit(Xs, ys)  # restarted process: resume and finish
    assert sgd.n_iter_ == 10 and np.all(np.isfinite(sgd.coef_))
    assert not os.path.exists(ckpt_path)  # completed fit clears it
    print(f"[proc {pid}] preemption drill OK: stopped_at={stopped_at} "
          f"resumed_iters={sgd.n_iter_}", flush=True)

    # -- flagship 3 (round 3): CROSS-HOST packed adaptive search.  A 2-D
    # global mesh puts the cohort's stacked MODEL_AXIS across the process
    # boundary, so one vmapped program trains all candidates with its
    # model shards on different hosts (the reference's futures plane
    # spreads partial_fit tasks over cluster workers —
    # ``dask_ml/model_selection/_incremental.py :: _fit``).  Every
    # process runs the same fit (multi-controller): the single packed
    # unit per round keeps the collective order identical everywhere.
    from dask_ml_tpu.linear_model import SGDClassifier
    from dask_ml_tpu.model_selection import IncrementalSearchCV
    from dask_ml_tpu.model_selection._packing import (
        DISPATCH_STATS,
        reset_dispatch_stats,
    )

    mesh2 = dist.global_mesh(model_axis=2)
    set_mesh(mesh2)
    Xs2 = dist.shard_rows_global(Xl, mesh2)
    ys2 = dist.shard_rows_global(yl, mesh2)
    reset_dispatch_stats()
    search = IncrementalSearchCV(
        SGDClassifier(random_state=0, tol=None),
        {"alpha": [1e-5, 1e-4, 1e-3, 1e-2]},
        n_initial_parameters="grid", max_iter=3, patience=False,
        random_state=0,
    )
    search.fit(Xs2, ys2, classes=[0.0, 1.0])
    # packed evidence: each dispatch stepped the whole 4-model cohort
    assert DISPATCH_STATS["dispatches"] > 0, DISPATCH_STATS
    assert DISPATCH_STATS["models_stepped"] == (
        4 * DISPATCH_STATS["dispatches"]
    ), DISPATCH_STATS
    scores = [
        round(s, 6) for s in search.cv_results_["test_score"]
    ]
    print(f"[proc {pid}] search_scores={scores} "
          f"dispatch_stats={dict(DISPATCH_STATS)}", flush=True)

    # -- flagship 4: Hyperband ON THE FLEET with sequential brackets —
    # each bracket is one lockstep packed cohort at a time, so every
    # process issues identical collectives (concurrent brackets would
    # interleave nondeterministically across threads and deadlock)
    from dask_ml_tpu.model_selection import HyperbandSearchCV

    hb = HyperbandSearchCV(
        SGDClassifier(random_state=0, tol=None),
        {"alpha": [1e-5, 1e-4, 1e-3, 1e-2]},
        max_iter=4, aggressiveness=2, random_state=0,
        sequential_brackets=True,
    )
    hb.fit(Xs2, ys2, classes=[0.0, 1.0])
    print(f"[proc {pid}] hyperband_best={hb.best_score_:.6f} "
          f"n_models={hb.n_models_}", flush=True)

    # -- flagship 5 (round 5): the SAME ADMM + Lloyd programs over the
    # hierarchical ('dcn', 'data', 'model') mesh with the dcn axis
    # spanning the two processes (SURVEY.md §2.3 multi-slice mesh).  The
    # row-shard count is identical to the flat mesh (2 dcn × 4 data = 8),
    # so the consensus math is the same program and the results must
    # agree with the flat-mesh fits to fp tolerance — proving the
    # ('dcn','data') axis-tuple collectives are correct end-to-end, not
    # just that the mesh builds.
    set_mesh(hmesh)
    Xh = dist.shard_rows_global(Xl, hmesh)
    yh = dist.shard_rows_global(yl, hmesh)
    assert Xh.n_samples == n_per * nproc
    beta_h = admm(Xh, yh, family=Logistic, lamduh=1e-4, max_iter=50,
                  mesh=hmesh)
    acc_h = float(accuracy(Xh.data, yh.data, Xh.mask, beta_h))
    assert acc_h > 0.9, f"DCN-mesh ADMM accuracy {acc_h}"
    np.testing.assert_allclose(
        np.asarray(beta_h), np.asarray(beta), atol=1e-4,
        err_msg="DCN-mesh ADMM diverged from the flat-mesh solve",
    )
    inertia_h = _lloyd_loop(
        Xh.data, Xh.mask, jnp.asarray(centers0),
        jnp.float32(1e-4), jnp.int32(20), scatter=_scatter,
    )[1]
    np.testing.assert_allclose(
        float(inertia_h), float(inertia), rtol=1e-5,
        err_msg="DCN-mesh Lloyd inertia diverged from the flat-mesh loop",
    )
    print(f"[proc {pid}] dcn_mesh OK: acc={acc_h:.3f}", flush=True)

    print(f"[proc {pid}] multihost OK: acc={acc:.3f} lloyd_iters={int(n_iter)}",
          flush=True)


def spawn_group(n_processes: int = 2, local_devices: int = 4,
                timeout_s: int = 720):
    """Spawn the worker group as subprocesses and collect results.

    The ONE subprocess harness (used by ``__graft_entry__.dryrun_multihost``
    and tests).  Each process's merged stdout/stderr is drained on its own
    thread — a later worker filling its pipe while the parent waits on an
    earlier one would otherwise block mid-collective and deadlock the whole
    SPMD group.  Returns ``[(returncode, output), ...]``; raises
    RuntimeError with all partial output on timeout.
    """
    import socket
    import subprocess
    import threading

    with socket.socket() as s:  # free port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # worker sets its own device count
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "dask_ml_tpu.core._multihost_worker",
             str(pid), str(n_processes), str(port), str(local_devices)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=repo_root,
        )
        for pid in range(n_processes)
    ]
    outs: list = [""] * n_processes
    timed_out = [False] * n_processes

    def drain(i, p):
        try:
            outs[i], _ = p.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired as e:
            timed_out[i] = True
            outs[i] = (e.stdout or "") if isinstance(e.stdout, str) else ""

    threads = [
        # no suppression needed: graftlint v2 resolves `drain` and proves
        # it host-only (p.communicate() pipe reads, no device dispatch)
        threading.Thread(target=drain, args=(i, p), daemon=True)
        for i, p in enumerate(procs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if any(timed_out):
        for p in procs:
            p.kill()
        for p in procs:
            p.wait()  # reap
        joined = "\n---\n".join(outs)
        raise RuntimeError(
            f"multihost group timed out after {timeout_s}s; partial output:\n{joined}"
        )
    return [(p.returncode, out) for p, out in zip(procs, outs)]


if __name__ == "__main__":
    main(
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3],
        int(sys.argv[4]) if len(sys.argv) > 4 else 4,
    )
