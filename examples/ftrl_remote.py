"""A keyed FTRL step over `remote_connect`, in two processes: this one holds
the table on its devices and serves it, a child process pulls the weights of
its minibatch's keys and pushes their raw gradients, and reads where the
server placed each op in the table's one order of Adds.

    python examples/ftrl_remote.py

Upstream's `Applications/LogisticRegression` (`objective_type=ftrl`) in its
distributed mode: the server keeps `(z, n)` a key and runs the FTRL-Proximal
step; the worker never sees the state, only weights."""

import os
import subprocess
import sys

import numpy as np

KEY_SPACE = 100_000

WORKER = """
import sys
import numpy as np
import multiverso_tpu as mv

client = mv.remote_connect(sys.argv[1])
table = client.table(int(sys.argv[2]))
rng = np.random.default_rng(0)
for step in range(3):
    keys = np.unique(rng.integers(0, %d, 2048)).astype(np.int32)
    weights = table.get(keys)               # after `last_ordinal` Adds
    seen = table.last_ordinal
    grads = rng.normal(0, 0.5, len(keys)).astype(np.float32)
    table.add(keys, grads)                  # one FTRL step of every key
    print(f"step {step}: pulled {len(keys)} weights after {seen} Adds "
          f"({np.count_nonzero(weights)} nonzero); my Add is number "
          f"{table.last_ordinal}", flush=True)
client.close()
""" % KEY_SPACE


def main():
    import multiverso_tpu as mv

    mv.init(remote_workers=1, ps_role="server")
    table = mv.create_table("ftrl", KEY_SPACE, alpha=0.1, beta=1.0,
                            lambda1=0.1, lambda2=1.0)
    endpoint = mv.serve("127.0.0.1:0")
    try:
        # the child must not take this process's devices
        subprocess.run([sys.executable, "-c", WORKER, endpoint,
                        str(table.table_id)], check=True,
                       env={**os.environ,
                            "JAX_PLATFORMS": "cpu"})
        n = np.asarray(table.get_state_device("n"))[:KEY_SPACE]
        print(f"{np.count_nonzero(n)} keys have taken a step")
    finally:
        mv.shutdown()


if __name__ == "__main__":
    main()
