"""The harness finds a configuration, a traffic mix, a cell and a
per-layer metric by name, and drives a run on the CPU."""

import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return tiny.write(tmp_path_factory.mktemp("bench"))


def _files(root):
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*.py")) if "__pycache__" not in p.parts}


@pytest.mark.parametrize("family", [None, "recorded_gqa"],
                         ids=["dense_gqa", "new_family"])
def test_additions_are_data(tmp_path, capsys, family):
    before = _files(tiny.ROOT / "bench")
    bench = tiny.write(tmp_path, family=family)
    res = tiny.run(capsys, bench, trace=1)
    assert res["correct"] is True
    assert res["attempted"] >= 3 and res["failed"] == 0
    # A dry run never prints device metrics; the added readers still ran.
    assert res["metrics"] == {}
    assert res["dry_layer_metrics"]["requests_prefilled"] > 0
    assert res["dry_layer_metrics"]["work_counted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["tokens_compared"]["value"] >= 24
    if family is not None:
        # The new family file alone took the model: its builder, its
        # reference and its counts ran, and no file there before changed.
        assert tiny.calls(bench, family) == set(tiny.CALLED)
        copied = _files(tmp_path / "bench")
        assert {p: copied[p] for p in before if p in copied} == {
            p: v for p, v in before.items() if p in copied}
    assert _files(tiny.ROOT / "bench") == before


@pytest.mark.parametrize("family", [None, "no_such_family", "../metrics/x"],
                         ids=["missing_key", "missing_file", "outside"])
def test_a_configuration_must_name_its_family_file(tmp_path, capsys, family):
    from bench.harness import main

    config = dict(tiny.CONFIG)
    del config["family"]
    if family is not None:
        config["family"] = family
    bench = tiny.write(tmp_path, config=config)
    with pytest.raises(SystemExit) as e:
        main(["--workload", "tiny.tinydoc", "--seed", "1", "--seconds", "1",
              "--trace", "0", "--benchmark", str(bench), "--dry-run"], 0.0)
    msg = str(e.value.code)
    assert "'family'" in msg and "bench/configs/tiny.json" in msg
    assert str(tmp_path / "bench" / "families") in msg
    assert capsys.readouterr().out == ""


def test_same_seed_same_inputs():
    from bench.traffic import Traffic

    spec = tiny.TRAFFIC
    a, b = Traffic(spec, 2**31 + 5, 512), Traffic(spec, 2**31 + 5, 512)
    c = Traffic(spec, 2**31 + 6, 512)
    assert (a.prompt(0, 3) == b.prompt(0, 3)).all()
    assert not (a.prompt(0, 3) == c.prompt(0, 3)).all()
    assert [a.prompt_len(0, i) for i in range(7)] == [64, 64, 96, 32, 90, 38, 64]
    assert a.warmup_waves() == [[64], [96], [32], [90], [38]]


def test_no_chip_no_result(tiny_bench, capsys):
    from bench.harness import main

    rc = main(["--workload", "tiny.tinydoc", "--seed", "1", "--seconds", "1",
               "--trace", "0", "--benchmark", str(tiny_bench)], 0.0)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_open_loop_run(tmp_path, capsys):
    traffic = {"arrival": {"kind": "open", "rate_per_s": 1.5,
                           "burst": {"size": 2, "every_s": 4.0}},
               "prompt": {"dist": "uniform", "min": 24, "max": 96},
               "output_tokens": 8,
               "warmup": [[96]]}
    res = tiny.run(capsys, tiny.write(tmp_path, traffic=traffic), seed=3)
    assert res["correct"] is True
    assert res["attempted"] >= 4 and res["failed"] == 0
    e2e = res["dry_end_to_end"]
    assert e2e["ttft_p50_s"] > 0 and e2e["prompt_tokens_s"] > 0


def test_prompt_tokens_count_the_prefill_inside_the_window():
    from bench.harness import end_to_end

    steps = [{"t0": 0.0, "t1": 4.0, "prefill_lens": [1000]},
             {"t0": 4.0, "t1": 5.0, "prefill_lens": []},
             {"t0": 5.0, "t1": 9.0, "prefill_lens": [800, 200]},
             {"t0": 9.0, "t1": 12.0, "prefill_lens": [600]}]
    rec = {"t0": 0.0, "t1": 10.0, "requests": [], "steps": steps}
    out = end_to_end(rec, 1.0)
    # Whole prefills of 1,000 and 1,000 tokens, and a third of the last.
    assert out["prompt_tokens_s"] == (1000 + 1000 + 600 / 3) / 10.0
    assert out["setup_s"] == 1.0 and "ttft_p50_s" not in out


def test_open_loop_and_length_distributions():
    from bench.traffic import Traffic

    spec = {"arrival": {"kind": "open", "rate_per_s": 4.0,
                        "burst": {"size": 3, "every_s": 2.0}},
            "prompt": {"dist": "loguniform", "min": 64, "max": 1536},
            "shared_prefix": {"count": 4, "tokens": 32},
            "output_tokens": {"min": 64, "max": 256},
            "warmup": [[64, 64], [1536]]}
    t = Traffic(spec, 9, 512)
    times = t.arrivals(10.0)
    assert times == Traffic(spec, 9, 512).arrivals(10.0)
    assert all(0 <= x < 10.0 for x in times) and times == sorted(times)
    assert times.count(0.0) == 3 and len(times) > 15
    lens = [t.prompt_len(0, i) for i in range(200)]
    assert 64 <= min(lens) and max(lens) <= 1536
    p = t.prompt(0, 5)
    assert len(p) == lens[5] and p.dtype.name == "int32"
    heads = {tuple(t.prompt(0, i)[:32]) for i in range(40)}
    assert len(heads) <= 4
    assert all(64 <= t.output_tokens(0, i) <= 256 for i in range(50))
    assert t.warmup_waves() == [[64, 64], [1536]]
