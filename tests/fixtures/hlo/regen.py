"""Regenerate the lowered/compiled HLO fixture dumps.

    python tests/fixtures/hlo/regen.py

Writes ``probe.stablehlo.txt`` (lowered StableHLO: the
``stablehlo.all_reduce`` / ``"stablehlo.all_to_all"(...)`` spellings)
and ``probe.compiled.txt`` (compiled CPU HLO: the hyphenated
``all-reduce(...)`` spellings, tuple-shaped all-to-all, operand
references like ``%all-to-all.2)`` that must NOT count) from one probe
program issuing exactly one collective of each lowerable kind.

``tpu_async.hlo.txt`` is hand-written (we have no TPU compiler in the
test environment) and NOT regenerated here — it pins the async
``-start``/``-done`` pair spelling, ``reduce-scatter``, and the
``metadata={op_name="...all-gather(..."}`` string hazard that the
quote guard in ``engine._COLLECTIVE_OP_RE`` exists for.

The committed dumps are test fixtures, not golden compiler output: a
jax upgrade that changes the text should regenerate them and re-pin
the counts in tests/test_hlo_counts.py if a spelling genuinely moved.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))


def build():
    mesh = Mesh(jax.devices()[:8], ("x",))

    def local(v):
        s = jax.lax.psum(v, "x")
        g = jax.lax.all_gather(v, "x", axis=0, tiled=True)
        t = jax.lax.all_to_all(v, "x", split_axis=1, concat_axis=1)
        r = jax.lax.ppermute(v, "x",
                             [(i, (i + 1) % 8) for i in range(8)])
        return s + g.sum(axis=0, keepdims=True) + t + r

    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("x", None),
                               out_specs=P("x", None)))
    sds = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    return fn.lower(sds)


def main():
    lowered = build()
    with open(os.path.join(_HERE, "probe.stablehlo.txt"), "w") as fh:
        fh.write(lowered.as_text())
    with open(os.path.join(_HERE, "probe.compiled.txt"), "w") as fh:
        fh.write(lowered.compile().as_text())
    print("wrote probe.stablehlo.txt / probe.compiled.txt")


if __name__ == "__main__":
    main()
