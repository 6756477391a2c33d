"""One serving replica for the router drill: a paged-KV engine behind
an EngineServer, supervised from the parent via heartbeat beacons.

Launched by ``SupervisedReplicaPool`` (tests/test_serving_router.py):
builds the tests' tiny deterministic LM, starts the
HTTP server on an ephemeral port, publishes the address atomically to
``AUTODIST_REPLICA_ADDR_FILE``, and beats
``AUTODIST_REPLICA_HB_DIR``/``AUTODIST_REPLICA_NAME`` with the engine's
tick count so the supervisor can tell WEDGED from slow.  Runs until
killed — replica death is the event under test.
"""
import json
import os
import sys
import time

import jax

from autodist_tpu.models.transformer import dense_attention
from autodist_tpu.models.transformer_lm import transformer_lm
from autodist_tpu.resilience.heartbeat import HeartbeatWriter
from autodist_tpu.serving import serve

VOCAB = 61


def main() -> int:
    addr_file = os.environ["AUTODIST_REPLICA_ADDR_FILE"]
    hb_dir = os.environ.get("AUTODIST_REPLICA_HB_DIR")
    name = os.environ.get("AUTODIST_REPLICA_NAME", "replica")
    seed = int(os.environ.get("AUTODIST_REPLICA_SEED", "0"))

    # The tests' deterministic tiny LM: every replica of a pool builds
    # IDENTICAL params from the seed, so greedy decode is replica-
    # independent — the property that makes re-routing output-exact.
    spec = transformer_lm(vocab_size=VOCAB, num_layers=2, num_heads=2,
                          head_dim=8, d_ff=32, max_len=48, seq_len=16,
                          attn_fn=dense_attention)
    params = spec.init(jax.random.PRNGKey(seed))
    srv = serve(spec, params, port=0, paged=True, slots=2, window=32,
                block_size=8, num_blocks=32, chunk=4)
    host, port = srv.address

    writer = None
    if hb_dir:
        writer = HeartbeatWriter(hb_dir, name, interval=0.5)
        writer.start()

    tmp = addr_file + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump({"host": host, "port": port, "pid": os.getpid()}, f)
    os.replace(tmp, addr_file)
    print(f"replica {name} listening on {host}:{port}", flush=True)

    eng = srv._engine
    while True:
        time.sleep(0.3)
        if writer is not None:
            writer.beat(step=int(eng.stats.ticks))


if __name__ == "__main__":
    sys.exit(main())
