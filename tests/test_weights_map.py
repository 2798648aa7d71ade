"""A shard's weights go from the file's pages to the device once
(`models/registry.py::_TimedReads`, `models/decoder.py::stack`, `on_device`):
a stored member is a view of the mapped file and any other is read as
before, a stacked leaf is a record of its parts until it is placed, the
fence trails by one leaf, and the tree that reaches the device is bit for
bit what `np.load` and `np.stack` gave."""
import io
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu import telemetry
from pipeedge_tpu.models import ShardConfig, decoder, registry
from pipeedge_tpu.telemetry import metrics as prom

FAMILIES = ["keye", "kimi", "qwen3-next", "lfm2", "laguna", "minicpm-sala",
            "nemotron-h"]
RNG = np.random.default_rng(53)
MEMBERS = {
    "float16": RNG.normal(size=(5, 12)).astype(np.float16),
    "float32": RNG.normal(size=(3, 4, 6)).astype(np.float32),
    "int32": RNG.integers(-9, 9, size=(17,)).astype(np.int32),
    "zero_d": np.float32(2.5),
    "empty": np.zeros((0, 7), np.float16),
    "one_row": RNG.normal(size=(1, 33)).astype(np.float16),
}


def _members():
    return {path: registry._MEMBERS.value(path=path)
            for path in ("mapped", "read")}


def _read_bytes():
    return telemetry._STARTUP_BYTES.value(phase="weights_read")


def _in_map(array) -> bool:
    return decoder._mapped(array)


@pytest.fixture(scope="module")
def stored(tmp_path_factory):
    """`np.savez`'s file: every member stored, behind a local header with
    a zip64 extra field (NumPy forces one on every member, so that one
    past 4 GiB can be written)."""
    path = str(tmp_path_factory.mktemp("stored") / "w.npz")
    np.savez(path, **MEMBERS)
    return path


@pytest.mark.parametrize("key", sorted(MEMBERS))
def test_a_stored_member_is_np_loads_array(stored, key):
    before = _members()
    with registry._TimedReads(stored) as weights, np.load(stored) as plain:
        mine, theirs = weights[key], plain[key]
    assert mine.dtype == theirs.dtype and mine.shape == theirs.shape
    assert mine.tobytes() == theirs.tobytes()
    assert not mine.flags.writeable or not _in_map(mine)
    after = _members()
    assert after["mapped"] - before["mapped"] == 1
    assert after["read"] == before["read"]


def test_the_local_headers_are_the_zip64_form_and_the_plain_form_maps_too(
        stored, tmp_path):
    """NumPy's members carry 20 bytes of zip64 extra field in the local
    header and none in the directory; a zip written without it (any other
    tool's) puts the data 20 bytes earlier. Both are found."""
    with open(stored, "rb") as file, zipfile.ZipFile(stored) as directory:
        for info in directory.infolist():
            file.seek(info.header_offset)
            _, name, extra = registry._LOCAL_HEADER.unpack(
                file.read(registry._LOCAL_HEADER.size))
            assert (name, extra, info.extra) == (len(info.filename), 20, b"")
    plain = str(tmp_path / "plain.npz")
    with zipfile.ZipFile(plain, "w") as out:
        for key, value in MEMBERS.items():
            body = io.BytesIO()
            np.save(body, value)
            out.writestr(key + ".npy", body.getvalue())
    before = _members()
    with registry._TimedReads(plain) as weights:
        assert sorted(weights) == sorted(MEMBERS)
        for key, value in MEMBERS.items():
            assert weights[key].tobytes() == np.asarray(value).tobytes()
    assert _members()["mapped"] - before["mapped"] == len(MEMBERS)


def test_the_file_is_a_mapping_of_its_keys_and_lets_go_of_the_map(stored):
    with registry._TimedReads(stored) as weights:
        assert len(weights) == len(MEMBERS)
        assert set(weights.keys()) == set(MEMBERS)
        assert "float16" in weights and "absent" not in weights
        with pytest.raises(KeyError):
            weights["absent"]
        view = weights["float32"]
    assert weights._map is None
    # a view outlives the block: the pages stay until the last one goes
    assert view.tobytes() == MEMBERS["float32"].tobytes()


def test_a_member_on_a_64_byte_boundary_is_copied_out(tmp_path):
    """The CPU backend keeps a host array on a 64-byte boundary as its own
    buffer: such a member must not reach `jnp.asarray` as a view of the
    file, or a file rewritten in place would change a live model."""
    path = str(tmp_path / "many.npz")
    np.savez(path, **{f"m{i}": np.full((64,), i, np.float32)
                      for i in range(96)})
    kinds = set()
    with registry._TimedReads(path) as weights:
        for key in weights:
            value = weights[key]
            assert value[0] == int(key[1:])
            if _in_map(value):
                assert value.ctypes.data % 64 and not value.flags.writeable
            else:
                assert value.ctypes.data % 64 == 0 or value.base is None
            kinds.add(_in_map(value))
            placed = jnp.asarray(value)
            if _in_map(value):
                assert placed.unsafe_buffer_pointer() != value.ctypes.data
    assert kinds == {True, False}       # offsets step by 4: both occur


def _compressed(tmp_path):
    path = str(tmp_path / "c.npz")
    np.savez_compressed(path, **MEMBERS)
    return path, "float16"


def _fortran(tmp_path):
    path = str(tmp_path / "f.npz")
    np.savez(path, turned=np.asfortranarray(MEMBERS["float16"]),
             **MEMBERS)
    return path, "turned"


def _version_3(tmp_path):
    """An `.npy` header of a version `numpy.lib.format` has no public
    reader for (a field name that is not latin-1)."""
    path = str(tmp_path / "v3.npz")
    np.savez(path, named=np.zeros(3, np.dtype([("α", np.float32)])))
    return path, "named"


@pytest.mark.parametrize("make", [_compressed, _fortran, _version_3],
                         ids=["compressed", "fortran", "npy-3.0"])
def test_what_is_not_an_arrays_bytes_in_c_order_is_read(tmp_path, make):
    path, key = make(tmp_path)
    before = _members()
    with registry._TimedReads(path) as weights, np.load(path) as plain:
        mine, theirs = weights[key], plain[key]
        assert not _in_map(mine)
        assert mine.dtype == theirs.dtype and mine.shape == theirs.shape
        assert mine.tobytes(order="A") == theirs.tobytes(order="A")
        assert mine.flags.f_contiguous == theirs.flags.f_contiguous
    after = _members()
    assert after["read"] - before["read"] == 1
    assert after["mapped"] == before["mapped"]


def test_an_object_array_is_refused_on_the_read_path(tmp_path):
    path = str(tmp_path / "o.npz")
    np.savez(path, things=np.array([{"a": 1}, None], dtype=object),
             plain=MEMBERS["int32"])
    before = _members()
    with registry._TimedReads(path) as weights:
        with pytest.raises(ValueError, match="allow_pickle"):
            weights["things"]
        assert _in_map(weights["plain"]) or weights["plain"].base is None
    after = _members()
    assert (after["read"] - before["read"],
            after["mapped"] - before["mapped"]) == (1, 1)


# -- stacked leaves -------------------------------------------------------------

def test_a_stack_of_host_arrays_is_a_record_until_asked_for_an_array():
    parts = [RNG.normal(size=(3, 4)).astype(np.float16) for _ in range(5)]
    layers = [decoder.stack(parts[:3]), decoder.stack(parts[2:])]
    assert isinstance(layers[0], decoder.Stacked)
    assert (layers[0].shape, layers[0].dtype) == ((3, 3, 4), np.float16)
    run = decoder.stack(layers)
    assert run.shape == (2, 3, 3, 4)
    want = np.stack([np.stack(parts[:3]), np.stack(parts[2:])])
    assert np.asarray(run).tobytes() == want.tobytes()
    assert np.asarray(run, np.float32).dtype == np.float32
    # 0-d parts, and the dtype np.stack would choose
    mixed = decoder.stack([np.float16(1).reshape(()), np.ones((), np.float32)])
    assert (mixed.shape, mixed.dtype) == ((2,), np.float32)
    assert np.array_equal(np.asarray(mixed), [1.0, 1.0])
    with pytest.raises(ValueError, match="same shape"):
        decoder.stack([parts[0], parts[0][:2]])


@pytest.mark.parametrize("turn", [
    lambda a: a.T, lambda a: a[:, 0].T, lambda a: np.moveaxis(a, 0, -1),
    lambda a: a[1:, :, 2:5], lambda a: a], ids=[
    "T", "column-T", "moveaxis", "slice", "as-stored"])
def test_a_turned_leaf_is_made_as_it_lies_and_turned_on_the_device(
        stored, turn):
    """A family's `.T` of a member stays a view; the leaf is made in the
    member's own memory order (a copy between like strides), goes over
    so and is turned on the device: what arrives is `np.stack` of the
    turned parts, bit for bit."""
    with registry._TimedReads(stored) as weights:
        view = turn(weights["float32"])
        want = np.stack([view, view, view])
        for leaf in (view, decoder.stack([view] * 3),
                     decoder.stack([decoder.stack([view] * 3)] * 2)):
            host = decoder._host(leaf, decoder._Rooms())
            assert host.shape == tuple(leaf.shape)
            if _in_map(view):
                assert host is not leaf and not _in_map(host)
                assert decoder._memory_order(host)[-view.ndim:] == tuple(
                    host.ndim - view.ndim + axis
                    for axis in decoder._memory_order(view))
            placed = decoder._put(host)
            assert placed.shape == host.shape
            assert np.asarray(placed).tobytes() == np.broadcast_to(
                want[0], host.shape).tobytes()


def test_a_stack_of_device_or_traced_values_is_jnp_stacks():
    out = decoder.stack([jnp.ones((2,)), jnp.zeros((2,))])
    assert isinstance(out, jax.Array) and out.shape == (2, 2)
    shapes = jax.eval_shape(
        lambda: decoder.on_device({"w": decoder.stack(
            [jnp.zeros((3, 2)), jnp.zeros((3, 2))])}, jnp.bfloat16))
    assert (shapes["w"].shape, shapes["w"].dtype) == ((2, 3, 2), jnp.bfloat16)


# -- the seven families ---------------------------------------------------------

def _whole(entry):
    return ShardConfig(1, entry.layers, is_first=True, is_last=True)


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """{family: a state-dict npz of drawn float16 values with every key
    its tiny model's loader asks for}."""
    out = {}
    for family in FAMILIES:
        entry = registry.get_model_entry("pipeedge/test-tiny-" + family)
        rng, tensors = np.random.default_rng(len(family)), {}

        def get(key, shape):
            if key not in tensors:
                tensors[key] = rng.normal(0, 0.5, shape).astype(np.float16)
            return tensors[key]
        entry.family._assemble(entry.config, _whole(entry), get, jnp.float32)
        out[family] = str(tmp_path_factory.mktemp(family) / "w.npz")
        np.savez(out[family], **tensors)
    return out


def _plain_stack(leaves):
    """`decoder.stack` as it was: the array, at once."""
    return (np if isinstance(leaves[0], np.ndarray) else jnp).stack(leaves)


def _same_trees(mine, theirs):
    flat_mine, tree_mine = jax.tree_util.tree_flatten_with_path(mine)
    flat_theirs, tree_theirs = jax.tree_util.tree_flatten_with_path(theirs)
    assert tree_mine == tree_theirs
    for (path, a), (_, b) in zip(flat_mine, flat_theirs):
        assert isinstance(a, jax.Array), path
        assert (a.dtype, a.shape) == (b.dtype, b.shape), path
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), path
    return flat_mine


@pytest.mark.parametrize("family", FAMILIES)
def test_a_familys_tree_is_bit_for_bit_np_loads_and_np_stacks(
        files, family, monkeypatch):
    entry = registry.get_model_entry("pipeedge/test-tiny-" + family)
    before, read0 = _members(), _read_bytes()
    with registry._TimedReads(files[family]) as weights:
        mine = entry.family.load_params(entry.config, _whole(entry), weights,
                                        dtype=jnp.bfloat16)
    with np.load(files[family]) as plain:
        arrays = {key: plain[key] for key in plain.files}
    # every member mapped (or, on a 64-byte boundary, copied from the map),
    # none read; the bytes are the file's arrays', each once, experts and all
    after = _members()
    assert (after["mapped"] - before["mapped"], after["read"]) \
        == (len(arrays), before["read"])
    assert _read_bytes() - read0 \
        == sum(value.nbytes for value in arrays.values())
    monkeypatch.setattr(decoder, "stack", _plain_stack)
    theirs = entry.family.load_params(entry.config, _whole(entry), arrays,
                                      dtype=jnp.bfloat16)
    flat = _same_trees(mine, theirs)
    assert {np.dtype(leaf.dtype).name for _, leaf in flat} \
        >= {"bfloat16"}
    # a layer's experts reached the device as one leaf a matrix
    assert any(leaf.ndim >= 4 for path, leaf in flat
               if "experts" in jax.tree_util.keystr(path)) \
        or family == "minicpm-sala"


@pytest.mark.parametrize("family", FAMILIES)
def test_a_drawn_tree_goes_through_the_same_stack(family, monkeypatch):
    entry = registry.get_model_entry("pipeedge/test-tiny-" + family)
    mine = entry.family.init_params(entry.config, _whole(entry), seed=3,
                                    dtype=jnp.bfloat16)
    monkeypatch.setattr(decoder, "stack", _plain_stack)
    theirs = entry.family.init_params(entry.config, _whole(entry), seed=3,
                                      dtype=jnp.bfloat16)
    _same_trees(mine, theirs)


def test_a_partitions_shard_reads_its_own_members_alone(files):
    entry = registry.get_model_entry("pipeedge/test-tiny-keye")
    read0 = _read_bytes()
    _, params, _ = registry.module_shard_factory(
        "pipeedge/test-tiny-keye", files["keye"], 5, entry.layers,
        dtype=jnp.bfloat16)
    assert "embeddings" not in params and "final" in params
    with np.load(files["keye"]) as plain:
        whole = sum(plain[key].nbytes for key in plain.files)
        table = plain["model.embed_tokens.weight"].nbytes
    assert 0 < _read_bytes() - read0 <= whole - table


# -- the fence ------------------------------------------------------------------

def test_the_fence_trails_by_one_leaf_and_ends_behind_the_last(monkeypatch):
    """At most two leaves are ever made and not yet waited for (the one
    whose transfer runs and the one the host is making), they are waited
    for in their order, and the last before `on_device` returns."""
    params = {f"leaf{i}": decoder.stack(
        [np.full((4, 3), i, np.float16)] * 2) for i in range(6)}
    params["bias"] = np.arange(5, dtype=np.float16)
    made, waited, open_most = [], [], [0]
    host, wait = decoder._host, jax.block_until_ready

    def making(leaf, rooms):
        made.append(len(made))
        open_most[0] = max(open_most[0], len(made) - len(waited))
        return host(leaf, rooms)

    def waiting(tree):
        waited.extend(id(leaf) for leaf in jax.tree_util.tree_leaves(tree))
        return wait(tree)

    monkeypatch.setattr(decoder, "_host", making)
    monkeypatch.setattr(jax, "block_until_ready", waiting)
    out = decoder.on_device(params, jnp.bfloat16, float32=(("bias",),))
    leaves = jax.tree_util.tree_leaves(out)
    assert len(made) == len(leaves) == 7
    assert waited == [id(leaf) for leaf in leaves]
    assert open_most[0] <= 2
    assert out["bias"].dtype == jnp.float32
    assert out["leaf3"].dtype == jnp.bfloat16 \
        and np.array_equal(np.asarray(out["leaf3"], np.float32),
                           np.full((2, 4, 3), 3.0))


def test_a_copy_out_of_the_map_is_weights_read_and_a_drawn_leafs_is_not(
        stored):
    seconds = telemetry._STARTUP_SECONDS
    with registry._TimedReads(stored) as weights:
        view = weights["float32"]
        if not _in_map(view):
            pytest.skip("the member fell on a 64-byte boundary")
        read0 = seconds.value(phase="weights_read")
        out = decoder.on_device({"w": decoder.stack([view, view])},
                                jnp.float32)
        assert seconds.value(phase="weights_read") > read0
    assert np.asarray(out["w"]).tobytes() == np.stack([view, view]).tobytes()
    read0 = seconds.value(phase="weights_read")
    decoder.on_device({"w": decoder.stack([MEMBERS["float32"]] * 2)},
                      jnp.float32)
    assert seconds.value(phase="weights_read") == read0
    assert "pipeedge_weights_members_total" in prom.REGISTRY.render()


# -- the rooms ------------------------------------------------------------------

class _At:
    def __init__(self, address):
        self.address = address

    def unsafe_buffer_pointer(self):
        return self.address


def test_a_room_is_taken_again_two_leaves_on_unless_the_device_kept_it():
    rooms = decoder._Rooms()
    first = rooms.take(4096)
    rooms.placed(_At(0))
    second = rooms.take(1000)
    rooms.placed(_At(first.ctypes.data + 4096))     # just past the other
    assert not np.shares_memory(first, second)
    assert second.nbytes == 1000 and second.base.nbytes >= 4096
    third = rooms.take(512)
    assert third.ctypes.data == first.ctypes.data and third.nbytes == 512
    rooms.placed(_At(third.ctypes.data + 8))        # the backend kept it
    assert rooms.take(64).ctypes.data == second.ctypes.data
    rooms.placed(object())                          # a traced leaf: no address
    fifth = rooms.take(64)
    assert not np.shares_memory(fifth, third)
    assert not np.shares_memory(rooms.take(64), second)
    grown = rooms.take(1 << 20)                     # too small: a new one
    assert grown.nbytes == 1 << 20 and not np.shares_memory(grown, fifth)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_many_leaves_through_two_rooms_arrive_whole(dtype):
    """Leaves of many sizes, cast or not (uncast, the CPU backend may keep
    a room as the leaf's own buffer): every one arrives as it was made,
    whatever was made in its room after it."""
    rng = np.random.default_rng(7)
    parts = {f"leaf{i:03d}": list(rng.normal(
        size=(2, int(rng.integers(1, 40)), 16)).astype(np.float32))
        for i in range(120)}
    out = decoder.on_device(
        {key: decoder.stack(pair) for key, pair in parts.items()}, dtype)
    for key, pair in parts.items():
        want = jnp.asarray(np.stack(pair)).astype(dtype)
        assert np.asarray(out[key]).tobytes() == np.asarray(want).tobytes()
