"""Counts what jax reports about compiling: backend-compile seconds and
the persistent cache's requests and hits (the counters chip_smoke.py
reads).  The harness reads it round the window: nothing may compile there."""

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
}
_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_requests = 0
        self.cache_hits = 0

    def _on_event(self, event, **_):
        key = _EVENTS.get(event)
        if key:
            setattr(self, key, getattr(self, key) + 1)

    def _on_duration(self, event, secs, **_):
        if event == _COMPILE:
            self.compile_s += secs
            self.compiles += 1

    def start(self):
        from jax import monitoring

        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def snapshot(self):
        return {
            "compile_s": self.compile_s,
            "compiles": self.compiles,
            "cache_requests": self.cache_requests,
            "cache_hits": self.cache_hits,
        }
