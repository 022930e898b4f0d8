"""What the files of the step's kernel share (``tests/test_sw_kernels*.py``):
the block shapes that exercise the tiling's edges, the walls, a
configuration in units of order one, and the helpers that hand the
kernel a block as a step finds it.  One file a worker under the driver's
``--dist loadfile``: the three tests that interpret the kernel case by
case for minutes each have a file of their own, so that no file is the
run's wall clock.
"""

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels

G = 2

# rows x width of one device's padded block, and the VMEM budget the
# tiling is given (None: its own): 52 rows leave a last tile of 4 under
# tiles of 48; 184 x 364 is the demo grid's block, one tile; 21 rows are
# no multiple of 8; the small budgets cut 100 rows into tiles of 8, 24;
# 256 columns fill their vector registers, so that a rotation's wrap
# lands in the ghost columns and not past them; 33 rows of 129 columns
# have their northern ghost rows in two tiles and their eastern ghost
# columns in two vector registers
SHAPES = {
    "aligned-36x256": (36, 256, None),
    "astride-33x129": (33, 129, None),
    "ragged-52x100": (52, 100, None),
    "demo-184x364": (184, 364, None),
    "odd-21x40": (21, 40, None),
    "tiles-of-8-100x140": (100, 140, 8 * 10 * 1024),
    "tiles-of-24-100x140": (100, 140, 24 * 10 * 1024),
}
WALLS = {"south": (True, False), "north": (False, True),
         "both": (True, True), "neither": (False, False)}
# round 1 in units of its own, so that every term of every tendency is
# of order one and float32's roundoff of order 1e-7: a rotation that
# changes by half from the first row to the last of the tallest block
UNIT = dict(dx=1.0, dy=0.8, gravity=1.0, depth=1.0, coriolis_f=1.0,
            coriolis_beta=4e-3, ghost=G)


def _budget(monkeypatch, shape, steps=1):
    """``SHAPES[shape]`` with its VMEM budget in place, scaled to the
    call's six arrays so that the tiles are the name's, of a walk of two
    ``steps`` as of one."""
    rows, width, budget = SHAPES[shape]
    if budget is not None:
        # the budget is no argument of the jitted call: a trace under
        # another budget, of the same shapes, would be taken for this one's
        sw_kernels.wide_step.clear_cache()
        monkeypatch.setattr(sw_kernels, "_VMEM_BLOCK_BUDGET", budget * 3)
        tile = sw_kernels.tile_rows(rows, width, jnp.float32, 6, steps)
        assert tile == int(shape.split("-")[2]) and rows > 3 * tile
    return rows, width


def _ring(shape, ring):
    """The cells of a padded block's ghost ring ``ring`` (2: outermost)."""
    inside = np.zeros(shape, bool)
    inside[G - ring:shape[0] - G + ring, G - ring:shape[1] - G + ring] = True
    inside[G - ring + 1:shape[0] - G + ring - 1,
           G - ring + 1:shape[1] - G + ring - 1] = False
    return inside


@dataclass(frozen=True)
class _Viscous(sw.SWConfig):
    """A configuration whose friction is set apart from its rotation:
    round 1 in ``UNIT`` with a friction strong enough to see (``dt * nu
    / dx**2`` is 0.02, not the 1e-4 of the unit rotation's own), so that
    an error in a stencil or a mask of either round is four orders of
    magnitude over float32's roundoff."""

    nu: float = 0.0

    @property
    def lateral_viscosity(self):
        return self.nu


def _as_a_step_finds_it(fresh, south, north, stale):
    """A block with fresh ghosts as the step's kernel is handed it: its
    ghost cells ``stale`` wherever a slab brings them, and the four
    slabs an exchange would bring, west, east, south, north.  Beyond a
    wall no neighbour sends: that slab is ``None``, as on a mesh one
    device high, and those ghost rows are the block's own but for their
    ends, which the x slabs bring."""
    fresh = np.asarray(fresh)
    block = fresh.copy()
    block[:, :G] = block[:, -G:] = stale
    if not south:
        block[:G] = stale
    if not north:
        block[-G:] = stale
    slabs = (fresh[:, :G], fresh[:, -G:],
             None if south else fresh[:G], None if north else fresh[-G:])
    return block, slabs


def _interpreted(cfg):
    """The kernel's keywords for ``cfg``, in Pallas's interpret mode."""
    return dict(
        nu=cfg.nu, dx=cfg.dx, dy=cfg.dy, dt=cfg.dt, gravity=cfg.gravity,
        coriolis_f=cfg.coriolis_f, coriolis_beta=cfg.coriolis_beta,
        interpret=True)
