"""The generators give the same cluster, waves and arrivals for one seed,
other ones for another seed, and the same amount of work for every seed."""

import json

import pytest

from harness import core, gen

SEEDS = (0, 1, 266486846, 2**31 + 17)


def _cell(name):
    cell = core.Cell(name, core.load_spec(held=True))
    cell.config["cluster"]["pods"] = 3000
    return cell


def _cluster_and_waves(seed):
    cell = _cell("pss-restricted.scan-rollout")
    cluster = gen.Cluster(seed, cell.config["cluster"], 3000)
    pods = json.dumps(cluster.pods())
    rollout = gen.Rollout(cluster, dict(cell.traffic, wave_pods=200))
    waves = json.dumps([rollout.next_wave() for _ in range(3)])
    return pods, waves, cluster


def _arrivals(seed):
    cell = _cell("pss-restricted-enforce.admission-steady")
    stream = gen.AdmissionStream(seed, cell.config["cluster"], cell.traffic, "window")
    offsets = stream.schedule(50.0, 4.0)
    return offsets, json.dumps([p for p, _ in stream.requests(len(offsets))])


def test_same_seed_same_cluster_waves_and_arrivals():
    for seed in SEEDS:
        assert _cluster_and_waves(seed)[:2] == _cluster_and_waves(seed)[:2]
        assert _arrivals(seed) == _arrivals(seed)


def test_different_seeds_differ():
    clusters = {_cluster_and_waves(s)[0] for s in SEEDS}
    waves = {_cluster_and_waves(s)[1] for s in SEEDS}
    arrivals = {tuple(_arrivals(s)[0]) for s in SEEDS}
    bodies = {_arrivals(s)[1] for s in SEEDS}
    assert len(clusters) == len(waves) == len(arrivals) == len(bodies) == len(SEEDS)


def test_every_seed_gets_the_same_amount_of_work():
    sizes = {tuple(sorted(d.replicas for d in _cluster_and_waves(s)[2].deployments))
             for s in SEEDS}
    assert len(sizes) == 1
    gaps = {tuple(sorted(round(b - a, 12) for a, b in
                         zip([0.0] + _arrivals(s)[0], _arrivals(s)[0])))
            for s in SEEDS}
    assert len(gaps) == 1
    for s in SEEDS:
        _, waves, _ = _cluster_and_waves(s)
        assert [len(w) for w in json.loads(waves)] == [200, 200, 200]


def test_replica_sizes_sum_and_range():
    sizes = gen.replica_sizes(20000, 1, 500, 1.2)
    assert sum(sizes) == 20000
    assert min(sizes) >= 1 and max(sizes) == 500


def test_waves_replace_pods_in_place():
    cell = _cell("pss-restricted.scan-rollout")
    cluster = gen.Cluster(7, cell.config["cluster"], 3000)
    before = {p["metadata"]["uid"] for p in cluster.pods()}
    rollout = gen.Rollout(cluster, {"wave_pods": 300, "new_template_share": 0.3})
    wave = rollout.next_wave()
    after = {p["metadata"]["uid"] for p in cluster.pods()}
    assert len(after) == len(before) == 3000
    assert {o["metadata"]["uid"] for o, _ in wave} <= before - after
    assert {n["metadata"]["uid"] for _, n in wave} <= after - before


def test_rate_phases_move_arrivals_and_keep_their_count():
    steady = [0.3, 0.9, 1.7, 2.2, 3.9]
    assert gen.modulate(steady, [[1.0, 1.0]]) == steady
    # on 0.5 s at 4x the mean rate, off 1.5 s: every arrival lands in an
    # on phase, and the mean-rate offsets map onto it in order
    on_off = gen.modulate(steady, [[0.5, 4.0], [1.5, 0.0]])
    assert len(on_off) == len(steady) and on_off == sorted(on_off)
    assert all(t % 2.0 <= 0.5 + 1e-12 for t in on_off)
    assert on_off[2] == pytest.approx(1.7 / 4)
    assert on_off[4] == pytest.approx(2.0 + (3.9 - 2.0) / 4)
    with pytest.raises(ValueError):
        gen.modulate(steady, [[0.5, 1.0], [1.5, 0.0]])
