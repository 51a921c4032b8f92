"""The engine's phase spans under ``jax.profiler``: the leaf phases are
host events of their plain names, and its enclosing spans are not there
to win every idle gap."""

from perfbench import trace


def test_mirrored_spans_reach_the_host_plane(tmp_path):
    import gc

    import jax

    from repro import obs
    from repro.obs import tracing
    from repro.serve.batching import BatchingEngine, BatchingOptions

    was = obs.enabled()
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng = BatchingEngine(BatchingOptions(max_batch=2,
                                             chain=("megakernel",)))
        reqs = [eng.submit(b"m%d" % i) for i in range(3)]
        for r in reqs:
            r.result(timeout=300)
        eng.close()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
        (obs.enable if was else obs.disable)()
        obs.reset()
    planes = trace.read(trace.find_xplane(str(tmp_path)))
    host = {name for plane, lines in planes if plane == trace.HOST_PLANE
            for _, events in lines for name, _, _ in events}
    assert tracing.PROFILER_SPANS <= host
    assert not host & {"bucket_feed", "device_absorb", "resilient_execute",
                       "registry_observe", "request", "queue_wait",
                       "bucket_wait"}
