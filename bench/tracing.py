"""The profiler trace of a run's window, read back into plain events.

``DeviceTrace`` starts JAX's profiler (host TraceMe events and the device
planes; the Python tracer stays off, it would slow the host it measures),
and on stop reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:

- every event of every line of each ``/device:TPU:<i>`` plane, by line;
- the host events of the ``/host:CPU`` plane that say what the host was
  doing: the harness's own annotations (``bench.*``) and JAX's dispatch
  spans (``PjitFunction(...)``, ``DevicePut``);
- the offset between the trace's clock and the wall clock, from one
  annotation whose wall time is read while it is open, so that the
  program's own spans (``repro.obs``, wall-clock microseconds) can be put
  on the trace's clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import time

SYNC = "bench.clock"
WINDOW = "bench.window"
#: host events kept from the trace, by name prefix
HOST_SPANS = ("bench.", "PjitFunction(", "DevicePut")


@dataclasses.dataclass
class Extract:
    devices: dict        # plane name -> {line name: [(name, start, dur)]}
    host: list           # [(name, start_ns, dur_ns)]
    offset_ns: float     # wall-clock ns minus trace ns
    window: tuple        # (start_ns, end_ns) of the window annotation


class DeviceTrace:
    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.sync_ns = 0
        self._window = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(SYNC):
            self.sync_ns = time.time_ns()

    @contextlib.contextmanager
    def window(self):
        import jax

        with jax.profiler.TraceAnnotation(WINDOW):
            yield

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def stop(self) -> Extract:
        import jax

        jax.profiler.stop_trace()
        try:
            (path,) = glob.glob(os.path.join(
                self.log_dir, "plugins", "profile", "*", "*.xplane.pb"))
            return self._read(jax.profiler.ProfileData.from_file(path))
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)

    def _read(self, data) -> Extract:
        devices, host = {}, []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                devices[plane.name] = {
                    line.name: [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
                    for line in plane.lines}
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    host.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith(HOST_SPANS))
        sync = [t for name, t, _ in host if name == SYNC]
        win = [(t, t + d) for name, t, d in host if name == WINDOW]
        if not sync or not win:
            raise RuntimeError("the trace lost the harness's annotations")
        return Extract(devices, host, float(self.sync_ns - sync[0]), win[0])
