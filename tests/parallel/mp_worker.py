"""Multi-process worker + cluster launcher for multi-host tests.

Each rank of an ``N processes x M virtual CPU devices`` cluster joins via
``distributed_init`` (the ``jax.distributed`` rendezvous SURVEY §2.9 maps
the reference's NetworkManager.scala:59-84 ServerSocket ring onto),
builds the same deterministic fixture, trains data-parallel GBDT over the
*global* mesh, and rank 0 writes the resulting tree arrays for the
launcher to compare against single-process training.

Run one rank:
``python mp_worker.py <process_id> <num_processes> <port> <out.npz>
[devices_per_process]``

``launch_cluster`` is the shared harness used by both
``test_multihost.py`` and ``__graft_entry__.dryrun_multichip`` step 5.
"""
import os
import socket
import subprocess
import sys
import tempfile


def main() -> None:
    proc_id = int(sys.argv[1])
    num_procs = int(sys.argv[2])
    port = sys.argv[3]
    out_path = sys.argv[4]
    devices_per_process = int(sys.argv[5]) if len(sys.argv) > 5 else 4

    # Must precede any jax use: distributed_init forces the CPU platform
    # and the per-process device count before the backend initializes.
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
    from mmlspark_tpu.parallel.mesh import create_mesh, distributed_init

    init_kwargs = {}
    if os.environ.get("MP_WORKER_HEARTBEAT"):
        init_kwargs["heartbeat_timeout_seconds"] = int(
            os.environ["MP_WORKER_HEARTBEAT"])
    distributed_init(coordinator_address=f"127.0.0.1:{port}",
                     num_processes=num_procs, process_id=proc_id,
                     cpu_devices_per_process=devices_per_process,
                     **init_kwargs)

    import jax
    import numpy as np

    assert jax.process_count() == num_procs
    assert len(jax.devices()) == num_procs * devices_per_process, \
        len(jax.devices())

    from mmlspark_tpu.models.gbdt import train

    binned, y, bu, cfg = make_fixture()
    if os.environ.get("MP_WORKER_ITERS"):
        # failure-detection rig: a fit long enough to be killed mid-way
        import dataclasses
        cfg = dataclasses.replace(
            cfg, num_iterations=int(os.environ["MP_WORKER_ITERS"]))
    mesh = create_mesh()  # spans all processes: global device list
    print(f"[rank {proc_id}] fit starting", flush=True)
    res = train(binned, y, cfg, bin_upper=bu, mesh=mesh)

    # SURVEY §2.9 maps BOTH reference rendezvous planes here: the
    # LightGBM ring (dp-GBDT above) and the VW spanning-tree allreduce —
    # a sharded VW fit over the same process-spanning mesh
    vw_l2 = _vw_leg(mesh)
    # and the long-context plane: ring attention with the sequence
    # sharded across BOTH processes (ppermute rides the inter-process
    # transport the way it rides ICI/DCN on a pod)
    ring_err = _ring_leg()

    if jax.process_index() == 0:
        b = res.booster
        # .npz suffix on the temp name keeps np.savez from appending
        # its own; the rename makes the file's appearance atomic
        tmp = out_path + ".tmp.npz"
        np.savez(tmp,
                 split_feature=b.split_feature,
                 threshold_bin=b.threshold_bin,
                 node_value=b.node_value,
                 logloss=res.evals[-1]["train_binary_logloss"],
                 vw_l2=vw_l2, ring_err=ring_err)
        os.replace(tmp, out_path)


def _vw_leg(mesh) -> float:
    """Sharded VW regression across the process-spanning mesh; returns
    the training L2 (the launcher asserts it learned)."""
    import numpy as np

    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.vw import VowpalWabbitRegressor

    rng = np.random.default_rng(9)
    n, d = 1024, 10
    x = rng.normal(size=(n, d))
    w_true = rng.normal(size=d)
    y = x @ w_true + 0.1 * rng.normal(size=n)
    y = (y - y.mean()) / y.std()
    df = DataFrame({"features": x, "label": y})
    model = (VowpalWabbitRegressor(numPasses=8, learningRate=0.5,
                                   batchSize=8, interPassSync=True)
             .set_mesh(mesh).fit(df))
    pred = model.transform(df)["prediction"]
    return float(np.mean((pred - y) ** 2))


def _ring_leg() -> float:
    """Ring attention with the sequence sharded over ALL global
    devices (both processes); returns max |ring - dense|."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.parallel.attention import (dense_attention,
                                                 ring_attention)
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    sp = len(jax.devices())
    sp_mesh = create_mesh(MeshConfig(dp=1, sp=sp))
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 8 * sp, 2, 4)),
                           jnp.float32)
               for _ in range(3))
    ring = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, sp_mesh, causal=True))(q, k, v)
    want = dense_attention(q, k, v, causal=True)
    return float(jnp.max(jnp.abs(ring - want)))


def make_fixture():
    """The separated-gains fixture of test_distributed.py:51 — split
    gains an order of magnitude apart so reduction-order drift cannot
    flip any split; dp training must agree with single-process exactly."""
    import numpy as np

    from mmlspark_tpu.models.gbdt import TrainConfig
    from mmlspark_tpu.ops.binning import BinMapper

    rng = np.random.default_rng(42)
    n = 4096
    x = np.stack([
        rng.normal(size=n) * 1.0,
        rng.normal(size=n) * 1.0 + 3.0,
        rng.uniform(-1, 1, size=n),
    ], axis=1)
    left_y = x[:, 1] > 3.0
    right_y = x[:, 1] <= 3.0
    logit = np.where(x[:, 0] > 0.5, 4.0 * right_y - 2.0,
                     4.0 * left_y - 2.0)
    y = (logit + rng.normal(size=n) * 0.2 > 0).astype(np.float64)
    bm = BinMapper.fit(x, max_bin=63)
    binned = bm.transform(x)
    cfg = TrainConfig(objective="binary", num_iterations=5, num_leaves=4,
                      max_depth=2, min_data_in_leaf=20)
    return binned, y, bm.bin_upper_values(cfg.max_bin), cfg


def launch_cluster(num_procs: int, out_path: str,
                   devices_per_process: int = 4,
                   timeout: float = 420.0):
    """Start ``num_procs`` ranks of this worker; wait for all.

    Returns ``(exit_codes, logs)``. Worker output goes to temp FILES,
    not pipes — with every rank joined in collectives, one rank blocking
    on a full pipe buffer would stall the whole cluster.
    """
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()

    env = dict(os.environ)
    # each rank must configure its own (CPU) backend — scrub any
    # parent-process forcing so distributed_init's path is what runs
    env.pop("XLA_FLAGS", None)

    worker = os.path.abspath(__file__)
    procs = []
    log_files = []
    for rank in range(num_procs):
        lf = tempfile.NamedTemporaryFile(
            mode="w+", suffix=f".rank{rank}.log", delete=False)
        log_files.append(lf)
        procs.append(subprocess.Popen(
            [sys.executable, worker, str(rank), str(num_procs), str(port),
             out_path, str(devices_per_process)],
            stdout=lf, stderr=subprocess.STDOUT, env=env))
    rcs = []
    timed_out = False
    for p in procs:
        try:
            rcs.append(p.wait(timeout=timeout))
        except subprocess.TimeoutExpired:
            timed_out = True
            for q in procs:
                q.kill()
            rcs.append(p.wait())
    logs = []
    for lf in log_files:
        lf.flush()
        lf.seek(0)
        logs.append(lf.read())
        lf.close()
        os.unlink(lf.name)
    if timed_out:
        raise TimeoutError(
            "multi-process cluster timed out; logs:\n" +
            "\n====\n".join(log[-4000:] for log in logs))
    return rcs, logs


def run_and_check(num_procs: int = 2, devices_per_process: int = 4) -> None:
    """Launch a cluster, then train single-process in THIS process and
    assert the trees agree — shared by the test and the dryrun."""
    import numpy as np

    from mmlspark_tpu.models.gbdt import train

    with tempfile.TemporaryDirectory() as td:
        out_path = os.path.join(td, "mp.npz")
        rcs, logs = launch_cluster(num_procs, out_path,
                                   devices_per_process=devices_per_process)
        assert rcs == [0] * num_procs, (
            "multi-host worker failed:\n" + "\n====\n".join(
                log[-4000:] for log in logs))
        assert os.path.exists(out_path), "rank 0 wrote no result"

        binned, y, bu, cfg = make_fixture()
        res = train(binned, y, cfg, bin_upper=bu)
        got = np.load(out_path)
        np.testing.assert_array_equal(res.booster.split_feature,
                                      got["split_feature"])
        np.testing.assert_array_equal(res.booster.threshold_bin,
                                      got["threshold_bin"])
        np.testing.assert_allclose(res.booster.node_value,
                                   got["node_value"], atol=1e-5)
        assert abs(res.evals[-1]["train_binary_logloss"]
                   - float(got["logloss"])) < 1e-5
        # VW sharded fit across both processes learned the linear task
        assert float(got["vw_l2"]) < 0.5, float(got["vw_l2"])
        # cross-process ring attention matches dense
        assert float(got["ring_err"]) < 1e-4, float(got["ring_err"])


if __name__ == "__main__":
    main()
