"""Starting and stopping the profiler for a traced run, and turning
what it wrote into the reduced summary. Traces go to a directory under
``TMPDIR`` (the driver gives each side its own), are reduced and
deleted; failure raises ``TraceError``."""

from __future__ import annotations

import shutil
import tempfile
from typing import Dict, Optional, Sequence

from . import xplane


class TraceWindow:
    def __init__(self) -> None:
        self.dir: Optional[str] = None
        self.active = False
        self.done = False

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="cellbench-trace-")
        try:
            # the Python tracer multiplies the host's work in the very
            # loop being traced and the trace's size; host spans come
            # from TraceAnnotation and the runtime's own TraceMe events
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
        except Exception as e:
            raise xplane.TraceError("cannot start the profiler: %s" % e)
        self.active = True

    def stop(self) -> None:
        import jax

        if not self.active:
            return
        self.active = False
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            raise xplane.TraceError("cannot stop the profiler: %s" % e)
        self.done = True

    def summary(self, prefer: Sequence[str] = ()) -> Dict:
        """Reduce and delete. Raises TraceError where nothing usable
        was written."""
        if not self.done or self.dir is None:
            raise xplane.TraceError("the traced window never closed")
        try:
            trace = xplane.read(xplane.find_xplane(self.dir))
            return xplane.summarize(trace, prefer=prefer)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def abandon(self) -> None:
        """Error path: leave no profiler running and no files behind."""
        try:
            self.stop()
        except xplane.TraceError:
            pass
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)
