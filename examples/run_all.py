"""Examples runner — the reference's notebook-E2E analog
(``nbtest/NotebookTests.scala`` runs every sample notebook as a job; here
every ``examples/*.py`` runs as a subprocess and must print
``EXAMPLE_OK <name>``).

The runner imports no JAX and each example pins the CPU platform
(``_common.py``), so nothing here takes a chip.

Usage: ``python examples/run_all.py [pattern]``; exits non-zero if any
example fails. Each example gets a timeout and one flaky retry, mirroring
the reference CI's retry policy (``pipeline.yaml:406-408``).
"""

from __future__ import annotations

import fnmatch
import os
import subprocess
import sys
import time

EXAMPLES_DIR = os.path.dirname(os.path.abspath(__file__))
TIMEOUT_S = int(os.environ.get("MMLSPARK_TPU_EXAMPLE_TIMEOUT", "600"))
RETRIES = 1


def discover(pattern: str = "*") -> list[str]:
    return sorted(
        f for f in os.listdir(EXAMPLES_DIR)
        if f.endswith(".py") and not f.startswith(("_", "run_"))
        and fnmatch.fnmatch(f, pattern))


def run_one(name: str) -> tuple[bool, float, str]:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(EXAMPLES_DIR, name)],
            cwd=EXAMPLES_DIR, env=env, capture_output=True, text=True,
            timeout=TIMEOUT_S)
        out = proc.stdout + proc.stderr
        ok = proc.returncode == 0 and "EXAMPLE_OK" in proc.stdout
    except subprocess.TimeoutExpired as e:
        out = f"TIMEOUT after {TIMEOUT_S}s\n" + str(e.stdout or "")
        ok = False
    return ok, time.monotonic() - t0, out


def main() -> int:
    pattern = sys.argv[1] if len(sys.argv) > 1 else "*"
    names = discover(pattern)
    if not names:
        print(f"no examples match {pattern!r}")
        return 2
    failures = []
    for name in names:
        for attempt in range(RETRIES + 1):
            ok, dt, out = run_one(name)
            if ok:
                print(f"PASS  {name}  ({dt:.1f}s"
                      + (", retry" if attempt else "") + ")")
                break
            if attempt < RETRIES:
                print(f"FLAKY {name} — retrying")
        else:
            print(f"FAIL  {name}  ({dt:.1f}s)\n{out[-2000:]}")
            failures.append(name)
    print(f"\n{len(names) - len(failures)}/{len(names)} examples passed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
