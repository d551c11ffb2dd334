"""The benchmark's own host spans, around its calls into each layer of the
program. A span is (name, start, end) on `time.perf_counter_ns`; while a
profiler trace is being taken it is also a `jax.profiler.TraceAnnotation`,
so that it lies on the device trace's clock."""
import contextlib
import time

import jax


class Spans:
    def __init__(self):
        self.records = []          # (name, start_ns, end_ns)
        self.annotate = False      # True while a profiler trace is open

    @contextlib.contextmanager
    def span(self, name):
        note = jax.profiler.TraceAnnotation(name) if self.annotate \
            else contextlib.nullcontext()
        with note:
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter_ns()))

    def mark(self):
        return len(self.records)

    def mean_ms(self, name, since=0, until=None):
        """Mean duration of the spans called `name` among records[since:until],
        or None where there is none."""
        d = [(e - s) / 1e6 for n, s, e in self.records[since:until]
             if n == name]
        return sum(d) / len(d) if d else None
