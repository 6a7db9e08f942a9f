"""bench/compare.py's numbers on hand-made changes."""
import numpy as np
import pytest

from bench import compare


def lanes(start, *after):
    return [[start, *after]]


START = {"w": np.zeros(4), "b": np.zeros(2)}
REF = {"w": np.array([1.0, 0.0, 0.0, 0.0]), "b": np.array([0.5, 0.0])}


def result(params, losses=(2.0, 1.0)):
    return {"losses": [list(losses)], "params": lanes(START, params, params)}


def test_equal_runs_read_zero():
    nums = compare.numbers(result(REF), result(REF))
    assert all(v == 0.0 for v in nums.values()), nums


def test_a_turned_change_shows_in_the_diff_only():
    turned = {"w": np.array([0.0, 1.0, 0.0, 0.0]), "b": REF["b"]}
    nums = compare.numbers(result(turned), result(REF))
    assert nums["step1_change_gap"] == 0.0
    assert nums["step1_change_diff"] == pytest.approx(np.sqrt(2.0))
    assert nums["last_change_diff"] == pytest.approx(np.sqrt(2.0))


@pytest.mark.parametrize("diff", [False, True])
def test_an_unchanged_state_reads_one(diff):
    frozen = {"params": lanes(START, START, START), "losses": [[2.0, 1.0]]}
    assert compare.change_gap(frozen["params"], result(REF)["params"], 1,
                              diff=diff) == pytest.approx(1.0)


def test_a_leaf_that_does_not_move_in_the_reference_is_left_out():
    ref = {"w": REF["w"], "b": np.array([1e-9, 0.0])}
    prog = {"w": REF["w"], "b": np.array([0.3, 0.0])}
    nums = compare.numbers(result(prog), result(ref))
    assert nums["step1_change_diff"] == 0.0
    assert nums["step1_change_gap"] == 0.0


def test_judge_fails_a_number_without_a_limit_and_skips_an_uncompared():
    values = dict.fromkeys(compare.NUMBERS, 0.01)
    limits = {n: {"limit": 0.1} for n in compare.NUMBERS}
    limits["loss_gap"] = {"limit": None}
    limits["first_loss_gap"] = {"compared": False}
    got = {c["name"]: c["ok"] for c in compare.judge(values, limits)}
    assert "first_loss_gap" not in got
    assert got.pop("loss_gap") is False
    assert all(got.values())


def test_trees_are_compared_leaf_by_leaf_by_path():
    from bench.reference import flat
    assert list(flat({"w0": np.ones(2), "b0": np.zeros(2)})) == ["b0", "w0"]
    tree = {"layers": [{"w": np.ones(3)}, {"w": np.zeros(3)}],
            "head": {"b": np.ones(1)}}
    got = flat(tree)
    assert list(got) == ["head/b", "layers/0/w", "layers/1/w"]
    moved = flat({"layers": [{"w": np.ones(3)}, {"w": np.full(3, 2.0)}],
                  "head": {"b": np.ones(1)}})
    # the program leaves layers/1/w where the reference moves it
    nums = compare.numbers({"losses": [[1.0]], "params": [[got, got, got]]},
                           {"losses": [[1.0]], "params": [[got, moved,
                                                           moved]]})
    assert nums["step1_change_gap"] == pytest.approx(1.0)
