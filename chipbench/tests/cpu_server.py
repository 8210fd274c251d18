"""Run chipbench/server.py on the CPU, for the tests, with one fault planted.

    python cpu_server.py <fault> <spec json>

The device check is replaced by a stand-in that accepts the CPU (the
harness's own check refuses it).  Faults, each planted where the timed path
produces its result:

  none    the program as it is
  token   every sampled token moved to the next id (the sampler)
  state   the paged decode block returns the page pool unchanged, so the
          KV it wrote is lost between dispatches
"""
import sys


def fake_chip(chips, root):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def plant(fault: str) -> None:
    from repro.serve.engine import ServeEngine
    if fault == "token":
        orig = ServeEngine._sample_fn

        def sample_fn(self):
            inner = orig(self)
            vocab = self.cfg.vocab

            def sample(rng, logits, uids, steps):
                toks, ok = inner(rng, logits, uids, steps)
                return (toks + 1) % vocab, ok
            return sample
        ServeEngine._sample_fn = sample_fn
    elif fault == "state":
        orig = ServeEngine._paged_decode_fn

        def paged_decode_fn(self):
            inner = orig(self)

            def decode(rng, params, pool, *rest):
                toks, ok, _, tel = inner(rng, params, pool, *rest)
                return toks, ok, pool, tel
            return decode
        ServeEngine._paged_decode_fn = paged_decode_fn
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault = sys.argv.pop(1)
    from chipbench import device, server
    device.require_chip = fake_chip
    plant(fault)
    sys.exit(server.main())
