"""On the mesh tier ``halo_exchange_2d`` writes a column slab narrower
than a lane tile as the whole lane tiles that hold it (``parallel/halo.py
_place``, ``_lane_tiles``).  That is data movement alone: the result is
a plain exchange's bit for bit, whatever the bits are, and which columns
are rewritten follows from shapes.  A 2x2 mesh of virtual devices against
an exchange written in numpy on the payload's bits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.parallel.halo import (
    LANES, _lane_tiles, _place, halo_exchange_2d, halo_exchange_2d_batch)

MESH = (2, 2)
WORDS = {"float32": np.uint32, "bfloat16": np.uint16, "int32": np.uint32}
# what arithmetic would not carry: a NaN with a payload, a negative
# zero, both infinities and a denormal, as float32 and as bfloat16 bits
SPECIAL = {
    np.uint32: [0x7FC01234, 0x80000000, 0x7F800000, 0xFF800000, 0x00000001],
    np.uint16: [0x7FC0, 0x8000, 0x7F80, 0xFF80, 0x0001],
}


def _comm():
    mesh = jax.make_mesh(
        MESH, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:4])
    return m.MeshComm.from_mesh(mesh)


def _bits(rows, nx, word, seed):
    """A 2x2 mesh's blocks as one array of random words, with the
    specials planted in every third row of the ghost columns, of the
    columns sent and of those beside them, and likewise by rows."""
    rng = np.random.default_rng(seed)
    blocks = rng.integers(
        0, np.iinfo(word).max, (MESH[0] * rows, MESH[1] * nx), dtype=word)
    if word is np.uint16:
        # the CPU backend carries bfloat16 through float32 on the wire,
        # which keeps a NaN and drops its payload and sign: one NaN here
        nan = (blocks & 0x7F80 == 0x7F80) & (blocks & 0x007F != 0)
        blocks[nan] = 0x7FC0
    special = np.array(SPECIAL[word], word)

    def plant(cells):
        i, j = np.indices(cells.shape)
        cells[...] = special[(i + j) % len(special)]

    for j in range(MESH[1]):
        edge = blocks[:, j * nx:(j + 1) * nx]
        plant(edge[::3, :12])
        plant(edge[::3, -12:])
    for i in range(MESH[0]):
        edge = blocks[i * rows:(i + 1) * rows]
        plant(edge[:6, ::5])
        plant(edge[-6:, ::5])
    return blocks


def _numpy_exchange(bits, rows, nx, w, periodic):
    """The exchange in the reference's order on a mesh's blocks: the two
    column slabs at full height, then the two row slabs at full width
    with the columns just received in them; a device at a wall keeps
    the ghosts it has."""
    per_y, per_x = periodic
    P, Q = MESH

    def cut(a):
        return [[a[i * rows:(i + 1) * rows, j * nx:(j + 1) * nx]
                 for j in range(Q)] for i in range(P)]

    old, out = cut(bits), cut(bits.copy())
    for i in range(P):
        for j in range(Q):
            if per_x or j > 0:
                out[i][j][:, :w] = old[i][(j - 1) % Q][:, -2 * w:-w]
            if per_x or j < Q - 1:
                out[i][j][:, -w:] = old[i][(j + 1) % Q][:, w:2 * w]
    mid = cut(np.block(out))
    for i in range(P):
        for j in range(Q):
            if per_y or i > 0:
                out[i][j][:w] = mid[(i - 1) % P][j][-2 * w:-w]
            if per_y or i < P - 1:
                out[i][j][-w:] = mid[(i + 1) % P][j][w:2 * w]
    return np.block(out)


def _library_exchange(bits, dtype, w, periodic, batch):
    """``halo_exchange_2d`` of each device's block, or ``_batch`` of
    three blocks of which this is the middle one, as the words it
    returns."""
    comm = _comm()
    spec = jax.P("y", "x")

    def local(x):
        if batch:
            out, _ = halo_exchange_2d_batch(
                [jnp.roll(x, 1, axis=0), x, jnp.roll(x, 2, axis=0)], comm,
                periodic=periodic, width=w)
            return out[1]
        return halo_exchange_2d(x, comm, periodic=periodic, width=w)[0]

    x = jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.dtype(dtype))
    out = jax.jit(jax.shard_map(
        local, mesh=comm.mesh, in_specs=spec, out_specs=spec))(x)
    return np.asarray(jax.lax.bitcast_convert_type(out, bits.dtype))


def _exchange_cases():
    """(rows, block width, ghost width, dtype, periodic, batch).  Small
    blocks over every width, block width and dtype; the benchmark's
    block once a dtype."""
    torus, walls = (True, True), (False, True)
    cases = []
    for nx, widths in ((100, (1, 2, 3)), (129, (1, 2, 3)), (130, (4,)),
                       (256, (1, 2, 3)), (3604, (1, 2, 3))):
        for w in widths:
            for dtype in WORDS:
                for periodic in (walls, torus):
                    for batch in (False, True):
                        cases.append((16, nx, w, dtype, periodic, batch))
    for dtype in WORDS:
        cases.append((1804, 3604, 2, dtype, walls, False))
        cases.append((1804, 256, 2, dtype, torus, False))
    cases.append((1804, 3604, 2, "float32", walls, True))
    return [pytest.param(
        "exchange", c,
        id="{}x{}-w{}-{}-{}-{}".format(
            c[0], c[1], c[2], c[3], "torus" if c[4][0] else "walls",
            "batch" if c[5] else "one")) for c in cases]


# (nx, start, w) -> (s0, s1), or None where the slab is written as it is
TILES = [
    ((3604, 0, 2), (0, 128)),        # the benchmark's west ghosts
    ((3604, 3602, 2), (3584, 3604)),  # its east ghosts: 20 columns of a tile
    ((14408, 14406, 2), (14336, 14408)),
    ((256, 254, 2), (128, 256)),
    ((256, 0, 1), (0, 128)),
    ((129, 0, 3), (0, 128)),
    ((129, 126, 3), (0, 129)),       # over the boundary: both tiles
    ((130, 126, 4), (0, 130)),
    ((130, 0, 4), (0, 128)),
    ((384, 126, 4), (0, 256)),
    ((384, 128, 4), (128, 256)),
    ((3604, 0, 127), (0, 128)),
    ((3604, 3477, 127), (3456, 3604)),
    ((3604, 0, 128), None),          # a whole tile wide: nothing to gain
    ((3604, 3404, 200), None),
    ((128, 126, 2), None),           # the block is one tile
    ((100, 98, 2), None),
    ((56, 0, 2), None),
]


def _tile_cases():
    return [pytest.param("tiles", (key, want),
                         id="tiles-nx{}-at{}-w{}".format(*key))
            for key, want in TILES]


@pytest.mark.parametrize("kind,case", _exchange_cases() + _tile_cases())
def test_a_narrow_column_slab_lands_bit_for_bit(kind, case):
    if kind == "tiles":
        (nx, start, w), want = case
        for rows in (16, 1804):
            assert _lane_tiles((rows, nx), (rows, w), (0, start)) == want
            # a slab that does not span the rows is not a column slab
            assert _lane_tiles((rows, nx), (rows - 1, w), (1, start)) is None
        if want is not None:
            s0, s1 = want
            assert s0 % LANES == 0 and (s1 % LANES == 0 or s1 == nx)
            assert s0 <= start and start + w <= s1 <= nx
        return
    rows, nx, w, dtype, periodic, batch = case
    word = WORDS[dtype]
    bits = _bits(rows, nx, word, seed=rows + nx + w)
    want = _numpy_exchange(bits, rows, nx, w, periodic)
    got = _library_exchange(bits, dtype, w, periodic, batch)
    # something moved, and specials among it
    assert (want != bits).any()
    assert np.isin(want[:, :w], SPECIAL[word]).any()
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def test_row_slabs_and_the_other_tiers_keep_the_plain_write():
    """Only a mesh-tier column slab takes the tile path: a row slab has
    no tile path by shape, and ``_place`` without ``tiles`` (the
    ``proc`` and ``self`` tiers) traces one ``dynamic_update_slice``."""
    assert _lane_tiles((1804, 3604), (2, 3604), (0, 0)) is None
    assert _lane_tiles((1804, 3604), (2, 3604), (1802, 0)) is None
    a, slab = jnp.zeros((16, 256)), jnp.ones((16, 2))
    region = np.s_[:, -2:]

    def names(**kw):
        jaxpr = jax.make_jaxpr(lambda a, s: _place(a, s, region, **kw))(a, slab)
        return [eqn.primitive.name for eqn in jaxpr.eqns]

    assert names() == ["dynamic_update_slice"]
    assert names(tiles=True).count("dynamic_update_slice") == 1
    assert "select_n" in names(tiles=True) and "pad" in names(tiles=True)
    np.testing.assert_array_equal(
        _place(a, slab, region, tiles=True), _place(a, slab, region))
