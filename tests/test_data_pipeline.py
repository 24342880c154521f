"""Data pipeline: the encoder-training pair stream."""
import numpy as np

from repro.data.stream import pair_batch


def test_pair_batch_salient_terms_shared():
    b = pair_batch(3, batch=4, seq=16, vocab=100, n_rel_terms=4)
    np.testing.assert_array_equal(np.asarray(b["query"])[:, :4],
                                  np.asarray(b["doc_pos"])[:, :4])
