"""The benchmark's yardstick: data and traffic generation, the closed
loop, the comparison that decides ``correct``, and the reduction from
traces, spans and counters to metrics."""
