"""The benchmark's generator copy draws what the program's generator draws."""
import jax
import numpy as np
import pytest

from chipbench import nexmark
from repro.streaming.generator import NexmarkConfig, generate_log


@pytest.mark.parametrize("skew,keys", [(0.0, 1000), (1.1, 25_000_000), (1.0, 5000)])
@pytest.mark.parametrize("seed", [0, 12345, 2**31 - 1])
def test_copy_reproduces_program_generator(skew, keys, seed):
    traffic = {"events_per_batch": 1024, "batches_per_call": 3,
               "rate_per_partition": 10_000.0, "key_skew": skew}
    got = nexmark.make_chunk_fn(traffic, keys, 2)(np.int32(seed))
    # jitted as the copy is, so that both prices go through one fused exp
    want = jax.jit(generate_log, static_argnums=0)(NexmarkConfig(
        num_partitions=2, num_batches=3, events_per_batch=1024,
        num_auctions=keys, key_skew=skew, seed=seed))
    for name, arr in zip(nexmark.FIELDS, got):
        np.testing.assert_array_equal(np.asarray(arr), np.asarray(getattr(want, name)),
                                      err_msg=name)


def test_chunk_seeds_take_any_whole_number():
    big = 2**31 + 12_345
    seeds = [nexmark.chunk_seed(big, i) for i in range(4)]
    assert seeds == [nexmark.chunk_seed(big, i) for i in range(4)]
    assert len(set(seeds)) == 4
    assert all(0 <= s < 2**31 for s in seeds)
    assert nexmark.chunk_seed(2**40, 0) != nexmark.chunk_seed(0, 0)
