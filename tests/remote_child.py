"""Child process for the cross-process remote-table test: connects to the
serving process, performs adds as an off-mesh worker, and exits 0 on success.
Usage: python remote_child.py <endpoint> <table_id> <n_adds> <delta>"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import numpy as np  # noqa: E402

import multiverso_tpu as mv  # noqa: E402


def main() -> int:
    endpoint, table_id, n_adds, delta = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), float(sys.argv[4]))
    client = mv.remote_connect(endpoint)
    assert client.worker_id >= 0, client.worker_id
    table = client.table(table_id)
    for _ in range(n_adds):
        table.add(np.full(table.size, delta, np.float32))
    # own contribution must be visible (async server applies in order)
    got = table.get()
    assert got.shape == (table.size,), got.shape
    assert np.all(got >= n_adds * delta - 1e-4), got
    client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
