"""Two-process collective runner (executed by test_cross_process.py).

Flow (reference `gen_comm_id_helper.cc:348` + `test_collective_base.py:32`
technique): rank 0 starts the C++ TCPStore; both ranks connect; rank 0
publishes the jax.distributed coordinator address through the store;
init_parallel_env brings up the 2-process CPU backend (gloo collectives);
a psum over the global 2-device mesh proves cross-process allreduce.
"""
import json
import os
import socket
import sys

rank = int(sys.argv[1])
store_port = int(sys.argv[2])

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
try:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:
    pass

# Load the native TCPStore WITHOUT importing the paddle_tpu package: nothing
# may touch the XLA backend before jax.distributed.initialize below.
import importlib.util  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "ptpu_native", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "_native", "__init__.py"))
_native = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_native)
TCPStore = _native.TCPStore

store = TCPStore("127.0.0.1", store_port, is_master=(rank == 0), world_size=2)
if rank == 0:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord_port = s.getsockname()[1]
    s.close()
    store.set("coordinator", f"127.0.0.1:{coord_port}")
else:
    store.wait(["coordinator"])
coordinator = store.get("coordinator").decode()

# paddle-style env -> init_parallel_env does jax.distributed.initialize
os.environ["PADDLE_TRAINER_ID"] = str(rank)
os.environ["PADDLE_TRAINERS_NUM"] = "2"
os.environ["PADDLE_TRAINER_ENDPOINTS"] = f"{coordinator},{coordinator}"

from paddle_tpu.parallel.env import init_parallel_env  # noqa: E402

init_parallel_env()
assert jax.process_count() == 2, jax.process_count()

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

devs = jax.devices()
mesh = Mesh(np.array(devs), ("dp",))
local = np.full((1, 4), float(rank + 1), np.float32)
garr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("dp")), local)


def allred(x):
    return lax.psum(x, "dp")


out = jax.jit(shard_map(allred, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                        check_vma=False))(garr)
local_out = np.asarray(out.addressable_data(0))

# store-side barrier + cross-check (TCPStore ADD used as the barrier count)
store.add("done", 1)
store.wait(["done"])

print(json.dumps({"rank": rank, "allreduce": local_out.tolist(),
                  "n_proc": jax.process_count()}))
