"""Child process that SERVES tables and then gets killed mid-session — the
server-side mirror of remote_crash_child.py: the parent connects a client,
does a round of traffic, SIGKILLs this process, and asserts the client
surfaces a clean error (reconnect deadline exhausted) instead of hanging.
Prints ``serving <endpoint> <table_id>`` once ready, then sleeps until
killed. Usage: python server_crash_child.py"""

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import numpy as np  # noqa: E402

import multiverso_tpu as mv  # noqa: E402


def main() -> int:
    mv.init(remote_workers=1)
    table = mv.create_table("array", 16, np.float32)
    endpoint = mv.serve("127.0.0.1:0")
    print(f"serving {endpoint} {table.table_id}", flush=True)
    time.sleep(600)  # parent SIGKILLs long before this
    return 1


if __name__ == "__main__":
    sys.exit(main())
