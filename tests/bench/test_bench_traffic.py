"""The traffic generators: seeded, deterministic, and the same work for
every seed; the training stream is the program's; the slot schedule
replays ``serve``'s."""
import numpy as np

from bench.traffic_gen import (MarkovStream, prompt_lengths, prompt_set,
                               serve_schedule)

SEED = 2**31 + 12345


def test_markov_stream_is_seeded_and_matches_the_program():
    from repro.data.pipeline import DataConfig, TokenPipeline

    a = MarkovStream(SEED, 64, 32, 4, 2.5)
    b = MarkovStream(SEED, 64, 32, 4, 2.5)
    prog = TokenPipeline(DataConfig(seed=SEED, vocab_size=64, seq_len=32,
                                    global_batch=4, markov_temp=2.5))
    for step in range(3):
        x, y, p = a.batch_at(step), b.batch_at(step), prog.batch_at(step)
        for k in ("tokens", "labels"):
            assert np.array_equal(x[k], y[k])
            assert np.array_equal(x[k], p[k])
    assert not np.array_equal(a.batch_at(0)["tokens"],
                              a.batch_at(1)["tokens"])
    assert not np.array_equal(
        a.batch_at(0)["tokens"],
        MarkovStream(SEED + 1, 64, 32, 4, 2.5).batch_at(0)["tokens"])


def test_prompt_sets_are_seeded_with_the_same_lengths_for_every_seed():
    kw = dict(median=256, sigma=0.8, lo=16, hi=1536)
    a = prompt_set(SEED, 64, 131072, **kw)
    assert a == prompt_set(SEED, 64, 131072, **kw)
    b = prompt_set(SEED + 1, 64, 131072, **kw)
    assert a != b
    assert list(map(len, a)) == list(map(len, b))
    assert sorted(map(len, a)) == sorted(prompt_lengths(64, **kw))
    assert all(0 <= t < 131072 for p in a for t in p)
    lengths = prompt_lengths(1001, **kw)
    assert min(lengths) >= 16 and max(lengths) == 1536
    assert sorted(lengths)[500] == 256


def test_serve_schedule_replays_the_serving_loop():
    import jax

    from repro.configs.base import get_arch
    from repro.launch.serve import serve
    from repro.models import model as M

    cfg = get_arch("olmo-1b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[1] * n for n in (3, 9, 2, 5, 7, 4)]
    res = serve(cfg, params, prompts, slots=4, buffer_len=32, max_new=4)
    steps = list(serve_schedule([len(p) for p in prompts], 4, 4))
    assert len(steps) == res.ticks
    assert steps[0] == [1, 1, 1, 1]
    # each request feeds len(prompt) + max_new - 1 tokens
    assert sum(len(s) for s in steps) == sum(len(p) + 3 for p in prompts)
    assert max(max(s) for s in steps) == 9 + 3
