"""Core hybrid-computing engine (the paper's contribution, generalized).

- work_sharing:   throughput-proportional work splits (paper §5.4.3)
- async_executor: chunk-pipelined concurrent execution + work stealing
- task_graph:     HEFT task-parallel scheduling (paper §5.4.4)
- calibration:    static + EWMA online throughput estimation (paper §4.5)
- hybrid_executor: executes work-shared plans over JAX device groups
- host_offload:   LUT/PRNG/pipeline host tasks (paper §4.6-§4.8)
- metrics:        gain & idle-time accounting (paper §5.1)
- device:         the device a call runs on (one notion of lane identity)
- compile_cache:  JAX's persistent compile cache at a fixed path

Submodules are imported by name: importing ``repro.core`` itself pulls
in nothing, so a process that only routes requests (``serve.router``)
never imports JAX.
"""
