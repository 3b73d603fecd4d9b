"""Record the small profiler trace ``test_benchmark.py`` reduces: a few
batched decodes on the chip, traced.

    python3 benchmark/tests/record_fixture.py <out.xplane.pb>
"""
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out: str) -> int:
    import jax
    import numpy as np

    from reporter_tpu import ops
    from reporter_tpu.matcher.hmm import NORMAL, RESTART
    rng = np.random.default_rng(0)
    B, T, K = 8, 64, 8
    dist = rng.uniform(0, 50, (B, T, K)).astype(np.float16)
    valid = np.ones((B, T, K), bool)
    route = rng.uniform(0, 100, (B, T, K, K)).astype(np.float16)
    gc = rng.uniform(0, 30, (B, T)).astype(np.float16)
    case = np.full((B, T), NORMAL, np.int32)
    case[:, 0] = RESTART
    args = (dist, valid, route, gc, case, np.float32(4.07), np.float32(3.0))
    np.asarray(ops.decode_batch(*args)[0])  # compile outside the trace
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            np.asarray(ops.decode_batch(*args)[0])
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        shutil.copy(path, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(jax.devices()[0].device_kind, os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
