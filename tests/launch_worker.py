"""Worker for the 2-process launch.py smoke test (run by
test_distributed_launch.py; the analog of the reference's
tests/book_distribute/notest_recognize_digits_mlp_dist.py:53-58).

Each process: init_multihost -> assert the GLOBAL mesh formed ->
one data-parallel train step of a paddle_tpu program over the global
mesh (feeds sharded on batch across processes, state replicated; XLA
inserts the cross-process all-reduce) -> print the replicated loss.
"""

import os
import sys

repo = sys.argv[1]
port = sys.argv[2]
proc_id = int(sys.argv[3])
n_procs = int(sys.argv[4])

# the spawning test sets JAX_PLATFORMS=cpu and the 2-device XLA flag in
# the child env; force them here too for direct runs
os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2")
sys.path.insert(0, repo)

import numpy as np  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

from paddle_tpu.distributed.launch import init_multihost  # noqa: E402

pid, n = init_multihost("127.0.0.1:%s" % port, n_procs, proc_id)
assert (pid, n) == (proc_id, n_procs), (pid, n)

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa

assert len(jax.local_devices()) == 2, jax.local_devices()
assert len(jax.devices()) == 2 * n_procs, jax.devices()  # global mesh

import paddle_tpu as ptpu  # noqa: E402
from paddle_tpu import layers  # noqa: E402

main, startup = ptpu.Program(), ptpu.Program()
main.random_seed = startup.random_seed = 3
with ptpu.program_guard(main, startup):
    x = layers.data("x", shape=[4])
    y = layers.data("y", shape=[1])
    pred = layers.fc(x, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    ptpu.optimizer.SGD(learning_rate=0.1).minimize(
        loss, startup_program=startup)
exe = ptpu.Executor()
exe.run(startup)

# identical global batch on every process; each feeds its LOCAL rows
rs = np.random.RandomState(0)
gx = rs.randn(8, 4).astype("float32")
gy = (gx.sum(1, keepdims=True) * 0.5).astype("float32")

fn, (state, feed_t) = exe.as_jax_function(
    main, {"x": gx[:2], "y": gy[:2]}, [loss])

mesh = Mesh(np.array(jax.devices()).reshape(-1), ("dp",))
batch_sh = NamedSharding(mesh, P("dp"))
repl = NamedSharding(mesh, P())

per = 8 // len(jax.devices())
lo = proc_id * 2 * per


def local_shard(garr):
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), garr[lo:lo + 2 * per])


feed = {"x": local_shard(gx), "y": local_shard(gy)}
state = {k: jax.device_put(v, repl) for k, v in state.items()}
step = jax.jit(fn, out_shardings=[repl])
out, = step(state, feed)
val = float(np.asarray(jax.device_get(out)))
# the mean over the GLOBAL batch == single-process reference value
ref_fn, (ref_state, _) = exe.as_jax_function(
    main, {"x": gx, "y": gy}, [loss])
ref = float(np.asarray(jax.jit(ref_fn)(ref_state,
                                       {"x": gx, "y": gy})[0]))
assert abs(val - ref) < 1e-5, (val, ref)
print("WORKER_OK %d loss=%.6f" % (proc_id, val), flush=True)
