"""The harness's batch generator is the program's TokenStream, bit for
bit, and every pod and step of the pool differs."""
import numpy as np
import pytest

from chipbench import data


@pytest.mark.parametrize("vocab", [50280, 6144, 257])
def test_matches_token_stream(vocab):
    from repro.data.pipeline import TokenStream

    seed = 2**31 + 99
    for shard, step in ((0, 0), (3, 11)):
        want = TokenStream(vocab_size=vocab, seq_len=300, batch_size=3,
                           seed=seed, shard=shard).batch(step)
        got = data.token_rows(seed, shard, step, 3, 300, vocab)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])


def test_pool_rows_all_differ():
    pool = data.batch_pool(5, 4, pods=2, rows=2, seq=64, vocab=1000)
    rows = [r.tobytes() for b in pool for pod in b["tokens"] for r in pod]
    assert len(set(rows)) == len(rows) == 16
