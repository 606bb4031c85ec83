"""Child process serving a DURABLE tiered-sparse table — the kill
target for the MV_TIER_KILL mid-demotion drill (docs/tiered_storage.md).

Usage: python tiered_kill_child.py <port> <wal_dir> <tier_dir> [--recover]

The parent arms the crash by exporting ``MV_TIER_KILL=before_commit`` or
``after_commit`` in THIS process's environment: the first cold-segment
write (triggered by Adds overflowing the tiny ``tier_resident_bytes``
budget below) SIGKILLs the process at that instant. Restarting with
``--recover`` must rebuild the exact logical state from snapshot+WAL —
the cold spill is disposable and is wiped on startup.

Prints ``serving <endpoint> <table_id>`` once ready, then sleeps until
killed."""

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import numpy as np  # noqa: E402

import multiverso_tpu as mv  # noqa: E402

#: Eight float32 rows of width 8 fit the hot tier; the ninth Add demotes.
RESIDENT_BYTES = 8 * 8 * 4
WIDTH = 8


def main() -> int:
    port, wal_dir, tier_dir = sys.argv[1], sys.argv[2], sys.argv[3]
    mv.init(ps_role="server", remote_workers=2, wal_dir=wal_dir,
            heartbeat_seconds=0.2, lease_seconds=30.0)
    # cold_bits=0 (raw): the drill checks durability ordering, and exact
    # float equality must survive a demote/fetch round-trip
    table = mv.create_table("tiered_sparse", 1 << 20, WIDTH, np.float32,
                            resident_bytes=RESIDENT_BYTES, cold_bits=0,
                            tier_dir=tier_dir)
    if "--recover" in sys.argv[4:]:
        mv.durable_recover([table])
    endpoint = mv.serve(f"127.0.0.1:{port}")
    print(f"serving {endpoint} {table.table_id}", flush=True)
    time.sleep(600)  # killed long before this
    return 1


if __name__ == "__main__":
    sys.exit(main())
