"""Spawnable compute-worker entry points for distributed-serving tests.

Run as ``python serving_worker_helpers.py <driver_host:port> <service>
<mode>``; kept importable (no pytest dependency) so subprocess workers are
real separate processes, mirroring the reference's executor JVMs.
"""

import os
import sys

# a serving worker is a child process and never takes the chip; compute
# is numpy
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from mmlspark_tpu.io.http.schema import HTTPResponseData  # noqa: E402
from mmlspark_tpu.serving import remote_worker_loop  # noqa: E402


def echo_with_pid(df):
    """Reply with '<pid>:<upper-cased body>' so tests can prove which
    process answered."""
    replies = np.empty(len(df), object)
    replies[:] = [
        HTTPResponseData(
            status_code=200,
            entity=f"{os.getpid()}:".encode()
            + (r.entity or b"").upper())
        for r in df["request"]]
    return df.with_column("reply", replies)


def lease_and_hang(df):
    """Take the lease, then never answer — simulates a worker that dies
    mid-processing (the kill test also SIGKILLs this process)."""
    import time
    time.sleep(3600)


MODES = {"echo": echo_with_pid, "hang": lease_and_hang}


def main():
    driver, service, mode = sys.argv[1], sys.argv[2], sys.argv[3]
    remote_worker_loop(driver, service, MODES[mode])


if __name__ == "__main__":
    main()
