"""Child process for tests/test_remote_timeline.py: a remote worker whose OWN
op-trace switch is off, making Add / Get pairs on a matrix table.
Usage: python remote_timeline_child.py <endpoint> <table_id> <pairs>
Prints one line, `worker <id> recorded <n>`: how many op records it kept in
its own ring (0: its switch is off; what it recorded went to the server)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import numpy as np  # noqa: E402

import multiverso_tpu as mv  # noqa: E402
from multiverso_tpu.dashboard import RING, Dashboard  # noqa: E402


def main() -> int:
    endpoint, table_id, pairs = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    assert not Dashboard.profile_annotations
    client = mv.remote_connect(endpoint)
    table = client.table(table_id)
    ids = np.arange(8, dtype=np.int32)
    for _ in range(pairs):
        table.add(np.ones((8, table.num_col), np.float32), row_ids=ids)
        got = table.get(ids)
        assert got.shape == (8, table.num_col), got.shape
    client.close()
    print(f"worker {client.worker_id} recorded {len(RING._kept())}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
