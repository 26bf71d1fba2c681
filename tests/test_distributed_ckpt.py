"""Two-process ``jax.distributed`` smoke (CPU, subprocess-launched):
sharded checkpoint save -> restore round-trip plus a shard-local index
refresh under the DP×TP mesh.

The CPU backend in this jaxlib cannot run cross-process XLA computations
(no multi-process collectives), so the smoke is arranged to need NONE —
which is exactly the sharded checkpoint path's design contract
(checkpoint/manager.py): arrays are created and restored with
``make_array_from_single_device_arrays`` over purely local device_puts,
save/publish coordination goes through the shared filesystem, and the
"shard-local refresh" leg runs each host's model-axis ShardedIndex slice
on a host-local mesh — legitimate, because under the DP×TP training mesh
the index spans the model axis ONLY (its state replicates over "data"),
so a host's refresh program never touches another host's devices.
Cross-host consistency of the DP replicas is asserted by exchanging
digests of the refreshed index state through the shared directory.
"""
import os
import socket
import subprocess
import sys
import textwrap

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_CHILD = """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import warnings; warnings.filterwarnings("ignore")
    import hashlib, json, time

    pid = int(sys.argv[1]); port = sys.argv[2]; wd = sys.argv[3]
    import jax
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2,
        process_id=pid,
    )
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from repro.checkpoint import manager as ckpt
    from repro.core import mips

    assert jax.process_count() == 2 and len(jax.devices()) == 4

    # DP x TP mesh: "data" spans the two processes, "model" is host-local
    mesh = jax.make_mesh(
        (2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )

    def place(arr, spec):
        # multi-process arrays WITHOUT collectives: slice per local device
        # and assemble (jax.device_put to a multi-process NamedSharding
        # would psum-assert equality across hosts, which CPU cannot run)
        s = NamedSharding(mesh, spec)
        bufs = [
            jax.device_put(arr[idx], d)
            for d, idx in s.addressable_devices_indices_map(arr.shape).items()
        ]
        return jax.make_array_from_single_device_arrays(arr.shape, s, bufs)

    rng = np.random.default_rng(0)
    embed = rng.standard_normal((64, 16)).astype(np.float32)
    moms = rng.standard_normal((64, 16)).astype(np.float32)
    ema = np.arange(12, dtype=np.float32).reshape(3, 4)
    state = {
        # P("data", ...): rows split ACROSS hosts -> each host writes its own
        "params": {"embed": place(embed, P("data", None))},
        # P("model", ...): replicated over "data" -> only process 0 writes,
        # process 1 restores from process 0's shard file
        "opt": {
            "m": place(moms, P("model", None)),
            # extended dtype through the sharded path, bitwise
            "ema": place(ema.astype(jnp.bfloat16), P()),
            "step": place(np.int32(7), P()),
        },
        "meta": {"step": 7, "data": {"step": 7, "seed": 0}},
    }

    mgr = ckpt.CheckpointManager(wd, keep=2, sharded=True)
    mgr.save_async(7, state)
    mgr.wait()
    deadline = time.monotonic() + 120
    while ckpt.latest_step(wd) != 7:  # process 0 publishes the manifest
        assert time.monotonic() < deadline, "checkpoint never published"
        time.sleep(0.05)

    shardings = jax.tree.map(
        lambda x: x.sharding, {k: v for k, v in state.items() if k != "meta"}
    )
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        {k: v for k, v in state.items() if k != "meta"},
    )
    got, meta, step = mgr.restore(target, shardings=shardings)
    assert step == 7 and meta["step"] == 7

    def check(want_np, have):
        for s in have.addressable_shards:
            np.testing.assert_array_equal(
                np.asarray(s.data), np.asarray(want_np[s.index])
            )
    check(embed, got["params"]["embed"])
    check(moms, got["opt"]["m"])
    assert got["opt"]["ema"].dtype == jnp.bfloat16
    have = np.asarray(got["opt"]["ema"].addressable_shards[0].data)
    assert have.tobytes() == np.asarray(ema.astype(jnp.bfloat16)).tobytes()
    assert int(np.asarray(got["opt"]["step"].addressable_shards[0].data)) == 7

    # ---- shard-local refresh under the DP x TP mesh ---------------------
    # the index spans the model axis only; each host refreshes its slice on
    # its local devices, and the "data"-axis replicas must stay bitwise
    # consistent across hosts (deterministic warm-started rebuild)
    local = Mesh(
        np.asarray(jax.local_devices()).reshape(1, 2), ("data", "model")
    )
    db = rng.standard_normal((1024, 16)).astype(np.float32)
    db /= np.linalg.norm(db, axis=1, keepdims=True)
    cfg = mips.IVFConfig(n_clusters=16, n_probe=4, kmeans_iters=2)
    index = mips.build_index(cfg, jnp.asarray(db), mesh=local)
    db2 = db + 0.02 * rng.standard_normal(db.shape).astype(np.float32)
    index = index.refresh(jnp.asarray(db2))
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(index):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    digest = h.hexdigest()
    with open(os.path.join(wd, f"digest_p{pid}.txt"), "w") as f:
        f.write(digest)
    other = os.path.join(wd, f"digest_p{1 - pid}.txt")
    deadline = time.monotonic() + 120
    while not os.path.exists(other):
        assert time.monotonic() < deadline, "peer digest never appeared"
        time.sleep(0.05)
    time.sleep(0.2)  # peer's write+close
    with open(other) as f:
        assert f.read() == digest, "DP replicas diverged after local refresh"
    print(f"OK-{pid}")
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_sharded_ckpt_and_local_refresh(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env.pop("XLA_FLAGS", None)
    script = textwrap.dedent(_CHILD)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid), str(port),
             str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=540)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"process {pid} failed:\n{out}\n{err}"
        assert f"OK-{pid}" in out, (out, err)
