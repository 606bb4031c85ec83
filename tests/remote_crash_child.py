"""Child process that CRASHES mid-training for the BSP stall-diagnostic test:
connects as a remote worker, completes one sync round (add + get), prints its
worker id, then dies without deregistering — simulating a worker crash whose
peers would previously hang with no diagnostic.
Usage: python remote_crash_child.py <endpoint> <table_id>"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import numpy as np  # noqa: E402

import multiverso_tpu as mv  # noqa: E402


def main() -> int:
    endpoint, table_id = sys.argv[1], int(sys.argv[2])
    client = mv.remote_connect(endpoint)
    table = client.table(table_id)
    table.add(np.ones(table.size, np.float32))
    table.get()
    print(f"round-1-done {client.worker_id}", flush=True)
    os._exit(9)  # crash: no deregister, no finish_train, socket torn down


if __name__ == "__main__":
    sys.exit(main())
