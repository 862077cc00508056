"""Unit tests of the versioned federated round-snapshot format (repro.ckpt).

Covers the ISSUE-7 satellites: the keypath-ambiguity fix (dict key "0" vs
sequence index 0), the format-version/schema checks with loud
``CheckpointMismatchError`` on unknown or missing keys and dtype flips, the
bitwise bf16 + ``NEVER``-sentinel + empty-array round-trip, retention GC,
and a hypothesis property test over arbitrary mixed-dtype pytrees (skipped
cleanly when hypothesis is not installed)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.ckpt import (
    FORMAT_VERSION,
    CheckpointMismatchError,
    latest_federated_round,
    list_federated_rounds,
    prune_federated_rounds,
    read_federated_meta,
    restore_federated_round,
    save_federated_round,
)
from repro.core.state import NEVER, init_client_state, to_bf16

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis — property test skips
    HAVE_HYPOTHESIS = False


def roundtrip(path, trees, arrays=None, meta=None, optional=()):
    save_federated_round(str(path), round_idx=0, trees=trees,
                         arrays=arrays or {}, meta=meta or {})
    return restore_federated_round(str(path), likes=trees, round_idx=0,
                                   optional=optional)


def assert_tree_bitwise(a, b):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = {k: v for k, v in jax.tree_util.tree_leaves_with_path(b)}
    assert len(la) == len(lb)
    for k, va in la:
        vb = lb[k]
        va, vb = np.asarray(va), np.asarray(vb)
        assert va.dtype == vb.dtype, f"{k}: {va.dtype} != {vb.dtype}"
        np.testing.assert_array_equal(va.view(np.uint8), vb.view(np.uint8))


class TestKeypathEncoding:
    def test_dict_key_vs_sequence_index_do_not_collide(self, tmp_path):
        """The old str()-based keypaths mapped {"0": x} and [x] to the same
        flat key; the typed d:/s: prefixes must keep them distinct."""
        tree = {"as_dict": {"0": jnp.ones((2,)) * 3.0},
                "as_list": [jnp.ones((2,)) * 7.0]}
        trees, _, _ = roundtrip(tmp_path, {"t": tree})
        np.testing.assert_array_equal(np.asarray(trees["t"]["as_dict"]["0"]),
                                      np.full((2,), 3.0, np.float32))
        np.testing.assert_array_equal(np.asarray(trees["t"]["as_list"][0]),
                                      np.full((2,), 7.0, np.float32))

    def test_dict_snapshot_refuses_list_template(self, tmp_path):
        """The actual old-format ambiguity: {"0": x} and [x] both flattened
        to the key "0", so a dict snapshot restored silently into a list
        template (or vice versa). Typed prefixes make it a loud mismatch."""
        save_federated_round(str(tmp_path), round_idx=0,
                             trees={"t": {"0": jnp.ones(2)}}, arrays={},
                             meta={})
        with pytest.raises(CheckpointMismatchError, match="keypaths"):
            restore_federated_round(str(tmp_path), likes={"t": [jnp.ones(2)]},
                                    round_idx=0)

    def test_schema_records_distinct_keypaths(self, tmp_path):
        save_federated_round(str(tmp_path), round_idx=0,
                             trees={"t": {"0": jnp.zeros(1),
                                          "lst": [jnp.zeros(1)]}},
                             arrays={}, meta={})
        schema = read_federated_meta(str(tmp_path), 0)["schema"]["trees"]["t"]
        assert "d:0" in schema
        assert "d:lst/s:0" in schema
        assert len(schema) == 2


class TestSchemaAndVersionErrors:
    def test_version_mismatch_is_loud(self, tmp_path):
        save_federated_round(str(tmp_path), round_idx=0,
                             trees={"t": jnp.zeros(2)}, arrays={}, meta={})
        jpath = os.path.join(str(tmp_path), "fedround_00000000.json")
        with open(jpath) as f:
            meta = json.load(f)
        meta["format_version"] = FORMAT_VERSION - 1
        with open(jpath, "w") as f:
            json.dump(meta, f)
        with pytest.raises(CheckpointMismatchError, match="format.*version"):
            restore_federated_round(str(tmp_path), likes={"t": jnp.zeros(2)},
                                    round_idx=0)

    def test_missing_required_tree_is_loud(self, tmp_path):
        save_federated_round(str(tmp_path), round_idx=0,
                             trees={"t": jnp.zeros(2)}, arrays={}, meta={})
        with pytest.raises(CheckpointMismatchError, match="missing required"):
            restore_federated_round(
                str(tmp_path), round_idx=0,
                likes={"t": jnp.zeros(2), "extra": jnp.zeros(2)})

    def test_optional_tree_skips_silently(self, tmp_path):
        save_federated_round(str(tmp_path), round_idx=0,
                             trees={"t": jnp.zeros(2)}, arrays={}, meta={})
        trees, _, _ = restore_federated_round(
            str(tmp_path), round_idx=0,
            likes={"t": jnp.zeros(2), "agg": jnp.zeros(2)},
            optional=("agg",))
        assert "agg" not in trees

    def test_unknown_snapshot_tree_is_loud(self, tmp_path):
        save_federated_round(str(tmp_path), round_idx=0,
                             trees={"t": jnp.zeros(2), "mystery": jnp.zeros(2)},
                             arrays={}, meta={})
        with pytest.raises(CheckpointMismatchError, match="mystery"):
            restore_federated_round(str(tmp_path), likes={"t": jnp.zeros(2)},
                                    round_idx=0)

    def test_keypath_disagreement_is_loud(self, tmp_path):
        save_federated_round(str(tmp_path), round_idx=0,
                             trees={"t": {"a": jnp.zeros(2)}}, arrays={},
                             meta={})
        with pytest.raises(CheckpointMismatchError, match="keypaths"):
            restore_federated_round(str(tmp_path),
                                    likes={"t": {"b": jnp.zeros(2)}},
                                    round_idx=0)

    def test_dtype_flip_is_loud_not_a_silent_cast(self, tmp_path):
        """A compact_state=True snapshot must refuse an f32 template."""
        save_federated_round(
            str(tmp_path), round_idx=0,
            trees={"t": jnp.zeros(3, jnp.bfloat16)}, arrays={}, meta={})
        with pytest.raises(CheckpointMismatchError, match="dtype"):
            restore_federated_round(str(tmp_path),
                                    likes={"t": jnp.zeros(3, jnp.float32)},
                                    round_idx=0)


class TestBitwiseRoundTrip:
    def test_client_state_f32_and_bf16_layouts(self, tmp_path):
        state = init_client_state(9, jnp.linspace(0.0, 0.5, 9))
        compact = to_bf16(state)
        trees, _, _ = roundtrip(tmp_path / "f32", {"cs": state})
        assert_tree_bitwise(trees["cs"], state)
        trees, _, _ = roundtrip(tmp_path / "bf16", {"cs": compact})
        assert_tree_bitwise(trees["cs"], compact)
        # the int32 NEVER sentinel survives the bf16 layout untouched
        np.testing.assert_array_equal(np.asarray(trees["cs"].last_selected),
                                      np.full(9, NEVER, np.int32))

    def test_bf16_bits_not_values(self, tmp_path):
        # values that differ in bf16 bit patterns but round the same in f16
        arr = jnp.asarray([1.0, -0.0, 3.0e38, 1e-40, float("inf")],
                          jnp.bfloat16)
        trees, _, _ = roundtrip(tmp_path, {"t": arr})
        assert_tree_bitwise(trees["t"], arr)

    def test_empty_arrays_and_infinities(self, tmp_path):
        tree = {"empty_f32": jnp.zeros((0,), jnp.float32),
                "empty_i32": jnp.zeros((0, 3), jnp.int32)}
        arrays = {"last_contact": np.full(4, -np.inf),
                  "nothing": np.zeros((0,), np.float64)}
        trees, arrs, _ = roundtrip(tmp_path, {"t": tree}, arrays=arrays)
        assert np.asarray(trees["t"]["empty_f32"]).shape == (0,)
        assert np.asarray(trees["t"]["empty_i32"]).shape == (0, 3)
        np.testing.assert_array_equal(arrs["last_contact"],
                                      np.full(4, -np.inf))
        assert arrs["nothing"].shape == (0,)

    def test_json_meta_floats_round_trip_exactly(self, tmp_path):
        vals = {"dur_sum": 0.1 + 0.2, "weight": 1.0 / 3.0, "neg": -1e-308}
        save_federated_round(str(tmp_path), round_idx=0, trees={}, arrays={},
                             meta={"extra": vals})
        back = read_federated_meta(str(tmp_path), 0)["extra"]
        for k, v in vals.items():
            assert back[k] == v  # bitwise: json round-trips f64 exactly


class TestRetention:
    def _snap(self, path, r):
        save_federated_round(str(path), round_idx=r,
                             trees={"t": jnp.full(2, float(r))},
                             arrays={}, meta={})

    def test_prune_keeps_newest_n(self, tmp_path):
        for r in range(6):
            self._snap(tmp_path, r)
        removed = prune_federated_rounds(str(tmp_path), keep_last=2)
        assert removed == [0, 1, 2, 3]
        assert list_federated_rounds(str(tmp_path)) == [4, 5]
        # json sidecars pruned too
        files = sorted(os.listdir(str(tmp_path)))
        assert files == ["fedround_00000004.json", "fedround_00000004.npz",
                         "fedround_00000005.json", "fedround_00000005.npz"]
        # survivors still restore
        trees, _, _ = restore_federated_round(
            str(tmp_path), likes={"t": jnp.zeros(2)}, round_idx=5)
        np.testing.assert_array_equal(np.asarray(trees["t"]),
                                      np.full(2, 5.0, np.float32))

    def test_keep_last_must_be_positive(self, tmp_path):
        with pytest.raises(ValueError, match="keep_last"):
            prune_federated_rounds(str(tmp_path), keep_last=0)

    def test_latest_and_list(self, tmp_path):
        assert list_federated_rounds(str(tmp_path)) == []
        assert latest_federated_round(str(tmp_path)) is None
        for r in (3, 1, 7):
            self._snap(tmp_path, r)
        assert list_federated_rounds(str(tmp_path)) == [1, 3, 7]
        assert latest_federated_round(str(tmp_path)) == 7


# ---------------------------------------------------------------------------
# Hypothesis property: arbitrary mixed-dtype pytrees round-trip bitwise.
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    def leaf_strategy():
        shapes = st.sampled_from([(0,), (1,), (3,), (2, 2), (4, 1), (0, 5)])

        def arr(dtype, elems):
            return shapes.flatmap(
                lambda s: st.lists(
                    elems, min_size=int(np.prod(s)), max_size=int(np.prod(s))
                ).map(lambda v: jnp.asarray(
                    np.asarray(v, dtype).reshape(s))))

        # width=32 bounds must be float32 values: the nearest ones to ±1e30
        # and ±3e38 give the same range
        f32_1e30 = float(np.float32(1e30))
        f32_3e38 = float(np.float32(3e38))
        f32 = arr(np.float32, st.floats(-f32_1e30, f32_1e30, width=32,
                                        allow_nan=False))
        i32 = arr(np.int32, st.integers(NEVER, 2**31 - 1))
        bf16 = arr(np.float32, st.floats(-f32_3e38, f32_3e38, width=32,
                                         allow_nan=False)
                   ).map(lambda a: a.astype(jnp.bfloat16))
        return st.one_of(f32, i32, bf16)

    def tree_strategy():
        return st.recursive(
            leaf_strategy(),
            lambda children: st.one_of(
                st.dictionaries(
                    st.sampled_from(["0", "1", "w", "b"]), children,
                    min_size=1, max_size=3),
                st.lists(children, min_size=1, max_size=3)),
            max_leaves=6)

    @settings(max_examples=25, deadline=None)
    @given(tree=tree_strategy(), data=st.data())
    def test_property_roundtrip_bitwise(tree, data, tmp_path_factory):
        path = tmp_path_factory.mktemp("prop")
        save_federated_round(str(path), round_idx=0, trees={"t": tree},
                             arrays={}, meta={})
        trees, _, _ = restore_federated_round(str(path), likes={"t": tree},
                                              round_idx=0)
        assert_tree_bitwise(trees["t"], tree)

else:

    def test_property_roundtrip_bitwise():
        pytest.importorskip(
            "hypothesis",
            reason="hypothesis not installed; property round-trip skipped")
