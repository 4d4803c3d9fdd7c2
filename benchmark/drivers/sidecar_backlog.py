"""Driver: the sidecar of ``sidecar.py`` (one ``ParseService`` at its
defaults in the run process, which holds the chip) under a standing
backlog.

Set-up, the shape warm-up and the check are ``sidecar.py``'s.  Only the
window differs: the load comes from ``benchmark/loadgen_backlog.py``, in
a child process that never imports JAX, where every session sends its
next flush as soon as its last one is answered.  ``lines_per_s`` is the
lines answered with rows inside the window over its seconds.
"""
import json
import os
import subprocess
import sys

from drivers.sidecar import BENCH
from drivers.sidecar import Driver as SidecarDriver


class Driver(SidecarDriver):
    def _start_child(self):
        spec = dict(self.load_spec(), host=self.service.host,
                    port=self.service.port)
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "loadgen_backlog.py"),
             "--spec", json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def window(self, seconds):
        self.child.stdin.write("GO\n")
        self.child.stdin.flush()
        line = self.child.stdout.readline()
        if not line.startswith("DONE "):
            raise RuntimeError(f"load generator failed: {line!r}")
        s = json.loads(line[5:])
        self.summary = s
        self._notes.append("loadgen " + json.dumps(s))
        return {"lines_per_s": s["lines_per_s"], "attempted": s["attempted"],
                "failed": s["failed"], "wall_s": seconds}
