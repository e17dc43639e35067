"""One-off of PR 24 (ran as build/diag_modes.py, from a checkout's root): one run of a
serving cell through benchmark/run.py with ``Server.window`` wrapped to print, before and
after the window, the CPU the main thread sits on, the load, the device's bytes in use and
largest free block (the ``kv`` / ``weights`` values are host-side buffer handles, not
device addresses: they say nothing).

    python3 tools/chip_calls/diag_modes.py --workload <cell> --seed <n> --seconds 8 --trace 0
"""
import ctypes
import os
import sys

sys.path.insert(0, os.getcwd())
from benchmark import run as brun                       # noqa: E402
from benchmark.runners import serve_ragged              # noqa: E402

orig_window = serve_ragged.Server.window
libc = ctypes.CDLL(None)


def info(server, tag):
    import jax

    cpu = libc.sched_getcpu()
    st = jax.devices()[0].memory_stats() or {}
    allowed = [line.split(":")[1].strip() for line in open("/proc/self/status")
               if line.startswith("Cpus_allowed_list")]
    print(f"# diag {tag}: cpu {cpu} allowed {allowed} "
          f"load {open('/proc/loadavg').read().split()[:3]} "
          f"in_use {st.get('bytes_in_use')} "
          f"largest_free {st.get('largest_free_block_bytes')}", flush=True)


def window(self, mix, seed, seconds):
    info(self, "before")
    out = orig_window(self, mix, seed, seconds)
    info(self, "after")
    return out


serve_ragged.Server.window = window
sys.exit(brun.main())
