"""Both DLRM engines on one Telemetry over a remote cold tier, run as a
SUBPROCESS on a FORCED 2-device CPU backend by tests/test_obs.py
(XLA_FLAGS must be set before jax import; the rest of the suite keeps
the real single device).

The serialized and the pipelined engine score zipf traffic against a
``RemoteStore`` (tables row-split over 2 simulated hosts, misses fetched
by the ``fetch_rows`` collective) while one ``Telemetry`` with the comm
sink installed records both.  The script exports the merged Chrome trace
to ``argv[1]`` and writes what the telemetry saw to ``argv[2]`` as JSON;
the tests read both.

    PYTHONPATH=src python tests/_obs_checks.py trace.json seen.json
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import sys

import numpy as np
import jax

from repro.cache import CacheConfig
from repro.configs import dlrm as dlrm_cfg
from repro.models import dlrm as dlrm_mod
from repro.obs import Telemetry
from repro.serving.engine import CTRRequest, make_dlrm_engine

T, R, D, L, CACHE_ROWS, HOSTS = 2, 256, 8, 4, 32, 2


def _config(depth):
    return dlrm_cfg.DLRMConfig(
        num_sparse_features=T, rows_per_table=R, embedding_dim=D,
        pooling=L, num_dense_features=4, bottom_mlp=(16, D),
        top_mlp=(16, 1), kernel_mode="reference",
        cache=CacheConfig(rows=CACHE_ROWS, policy="lru", cold_tier="remote",
                          remote_hosts=HOSTS, pipeline_depth=depth))


def _serve(engine, rng, rid0, n):
    for rid in range(rid0, rid0 + n):
        idx = np.minimum(rng.zipf(1.05, size=(T, L)) - 1, R - 1)
        engine.submit(CTRRequest(
            rid=rid, dense=rng.standard_normal(4).astype(np.float32),
            indices=idx.astype(np.int32), lengths=np.full(T, L, np.int32)))
    engine.run_to_completion()


def main(trace_path, seen_path):
    assert len(jax.devices()) == HOSTS, jax.devices()
    tel = Telemetry()
    tel.tracer.install_comm_sink()
    params = dlrm_mod.init_params(jax.random.key(0), _config(1))
    rng = np.random.default_rng(1)
    engines = [make_dlrm_engine(params, _config(depth), batch_size=4,
                                telemetry=tel) for depth in (1, 2)]
    try:
        for k, eng in enumerate(engines):
            _serve(eng, rng, rid0=100 * k, n=12)
    finally:
        tel.tracer.remove_comm_sink()
    tel.export_trace(trace_path)
    seen = {
        "latency_counts": {e.obs_name: tel.request_latency(e.obs_name).count
                           for e in engines},
        "timed_fetch_rows": sum(
            s.seconds > 0
            for s in tel.tracer.spans(lane="comm", name="fetch_rows")),
    }
    with open(seen_path, "w") as f:
        json.dump(seen, f)
    print(json.dumps(seen))


if __name__ == "__main__":
    main(*sys.argv[1:3])
