"""Operation counts and the traffic generator."""
import numpy as np

from bench import arrivals, flops

POLICY = {"hidden_channel": 4, "layer_trans": 2, "layer_gnn": 2,
          "layer_parsingnet": 2, "num_devices": 2}


def test_operation_count_by_hand_for_a_ten_node_graph():
    # V=10 nodes, E=12 edges, 5 input features, hidden 4, 2 devices.
    # input MLP 2*10*(5*4 + 4*4) = 720; two GCN layers 2*(2*10*16 +
    # 2*(2*12 + 10)*4) = 1184; edge scorer 2*12*(4*4 + 4*1) = 480; head
    # 2*10*(4*4 + 4*2) = 480.
    assert flops.step_forward(10, 12, 5, POLICY, first=True) == 2864
    assert flops.step_forward(10, 12, 5, POLICY, first=False) == 2144
    # forward once + backward twice, 3 chains, steps (first, later)
    assert flops.train_window(10, 12, 5, POLICY, chains=3, steps=2,
                              start_first=True) == 3 * 3 * (2864 + 2144)


def test_arrivals_and_picks_repeat_for_a_seed():
    a = arrivals.open_loop(2 ** 33 + 5, 40.0, 10.0, 12, 1.1)
    b = arrivals.open_loop(2 ** 33 + 5, 40.0, 10.0, 12, 1.1)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_every_seed_gets_the_same_work_in_another_order():
    (d1, p1), (d2, p2) = (arrivals.open_loop(s, 40.0, 10.0, 12, 1.1)
                          for s in (1, 2))
    assert len(d1) == len(d2) == 400
    gaps = arrivals.exponential_gaps(400, 40.0, 10.0)
    for d in (d1, d2):
        assert np.isclose(np.diff(d)[:, None], gaps[None]).any(1).all()
    assert np.array_equal(np.bincount(p1), np.bincount(p2))
    assert not np.array_equal(p1, p2)
    assert 0.0 <= d1.min() and d1.max() < 10.0
    counts = arrivals.zipf_counts(400, 12, 1.1)
    assert counts.sum() == 400 and np.all(np.diff(counts) <= 0)
