"""Per-worker daemon (paper §6): utilization sampling + completion events.

On the real testbed this is two threads — a 10 ms cgroup sampler and a
completion watcher that gRPCs (exec time, cold-start latency, vCPU/mem
utilization series) to the metadata store. The cost functions read
only the series' maxima, so the simulator reports those directly at
each completion (``Simulator._on_finish``) as an ``Observation`` in an
``InvocationRecord``, closing the feedback loop (Fig. 5 step 5).
"""

SAMPLE_INTERVAL_S = 0.010  # 10 ms cgroup sampling
