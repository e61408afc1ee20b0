"""Seconds jax spent in backend compiles and the persistent cache's hit and
miss counts, read from jax.monitoring: it sees every program the process
compiles or loads. (A copy of chip_smoke.CompileMeter, kept here so that no
later PR to the program can change what the benchmark counts.)"""
import collections


class CompileMeter:
    def __init__(self):
        import jax
        self.seconds = 0.0
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.events["backend_compiles"] += 1

    def _event(self, event, **kwargs):
        self.events[event.rsplit("/", 1)[-1]] += 1

    def mark(self) -> dict:
        return {"compile_s": self.seconds,
                "backend_compiles": self.events["backend_compiles"],
                "cache_hits": self.events["cache_hits"],
                "cache_misses": self.events["cache_misses"]}

    def since(self, mark: dict) -> dict:
        now = self.mark()
        return {k: now[k] - mark[k] for k in now}
