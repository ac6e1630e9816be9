"""Child process of ``test_bench_faults.py``: runs the four-stage tiny
pipeline cell on four CPU devices, soundly and with each fault, and prints
one JSON line per case."""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tinytree import run  # noqa: E402


def main(tree: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import pipeline as PL
    from repro.serving.scheduler import ContinuousBatcher

    assert len(jax.devices()) == 4, jax.devices()
    real_tick = PL.pipeline_decode_tick
    real_ppermute = jax.lax.ppermute
    real_sample = ContinuousBatcher._sample

    def state_unchanged(cfg, stage_params, mask, state, *a, **kw):
        real_tick(cfg, stage_params, mask, state, *a, **kw)
        return state

    def half_batch(cfg, stage_params, mask, state, *a, feed_valid=None, **kw):
        keep = jnp.logical_and(feed_valid, state.tick % 2 == 0)
        return real_tick(cfg, stage_params, mask, state, *a,
                         feed_valid=keep, **kw)

    def token_altered(self, req, ev):
        tok = real_sample(self, req, ev)
        return (tok + 1) % len(ev.logits) if len(req.generated) % 3 == 1 \
            else tok

    cases = {
        "sound": {},
        "no_exchange": {(jax.lax, "ppermute"): lambda x, *a, **k: x},
        "state_unchanged": {(PL, "pipeline_decode_tick"): state_unchanged},
        "half_batch": {(PL, "pipeline_decode_tick"): half_batch},
        "token_altered": {(ContinuousBatcher, "_sample"): token_altered},
    }
    originals = {(PL, "pipeline_decode_tick"): real_tick,
                 (jax.lax, "ppermute"): real_ppermute,
                 (ContinuousBatcher, "_sample"): real_sample}
    for name, patches in cases.items():
        for (obj, attr), fn in patches.items():
            setattr(obj, attr, fn)
        try:
            # a stalled fault never drains: wait 5 s, not a minute
            r = run(Path(tree), "tinypipe.closed", seconds=2.0, drain_s=5.0)
        finally:
            for (obj, attr), fn in originals.items():
                setattr(obj, attr, fn)
        print(json.dumps({"case": name, "correct": r["correct"],
                          "checks": r["checks"],
                          "stages": r["deployment"]["stages"]}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
