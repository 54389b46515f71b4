"""Sub-byte bin-matrix packing: the nibble (4-bit) + crumb (2-bit)
storage layouts.

``max_bin <= 16`` means every bin index of a feature group fits in 4
bits, so the HBM-resident ``(N, G)`` uint8 bin matrix wastes half its
bytes — and the bandwidth-bound histogram kernels read twice the HBM
they need (the LiteMORT compact-binning lever, PAPERS.md arxiv
2001.09419, on top of the GPU-histogram bandwidth analysis, arxiv
1706.08359).  ``max_bin <= 4`` tightens that to a CRUMB: four bin
indices per byte, a 4x read-stream cut.  This module is the ONE home
for the packed layout every layer shares: host-side construction
(dataset.py), the binary/shard caches (dataset_io.py,
sharded/cache.py), the quality profile's bincounts
(quality/profile.py), and the static layout parameters the device
kernels unpack by (ops/histogram.py, ops/partition.py,
ops/predict.py).

Layout — **three sections: crumb, nibble, byte**:

* groups are ordered NARROWEST-FIRST at construction
  (``Dataset._build_groups``): the first ``C`` groups each have
  ``num_bin <= 4`` (crumb groups), groups ``C..P`` have
  ``num_bin <= 16`` (nibble groups), the remaining ``G - P`` are
  wide;
* storage byte ``j < ceil(C/4)`` carries groups ``4j .. 4j+3`` in
  its four crumbs (group ``4j + k`` at bit ``2k``);
* nibble bytes follow: byte ``ceil(C/4) + j`` carries group
  ``C + 2j`` in its LOW nibble and ``C + 2j + 1`` in its HIGH
  nibble (the interleave keeps bundle-adjacent groups inside one
  byte);
* wide groups follow one byte each.

So storage column arithmetic is pure and static — ``byte_of(g)`` /
``shift_of(g)`` below — which is what lets the Pallas kernels unpack
crumbs and nibbles in-register with static shifts instead of carrying
an indirection table.  The full section geometry travels through the
device kernels as ONE static int, the **pack spec**
(``pack_spec(P, C) = P | C << 16``): every kernel's existing
``packed_groups`` static argument carries it unchanged, and a
crumb-free spec is numerically equal to the legacy plain-``P`` value
so every pre-crumb lowering (and its compiled-cache key) is
bit-preserved.

Modes (``Config.bin_packing``):

* ``8bit`` (default): no packed section — the legacy one-byte-per-
  group matrix, bit-compatible with every existing cache;
* ``4bit``: requires ``max_bin <= 16`` (config-level hard error).  A
  single feature too wide for a nibble is a loud construction error
  naming the group; a wide multi-feature EFB bundle splits out into
  the byte-wide section with a warning ("EFB-aware group re-packing"
  — the bundle keeps its 8-bit-identical membership and moves to the
  wide section, because re-forming bundles at nibble width was
  measured to break byte-exact tree parity: a different bundling
  reconstructs default-bin mass through a different FixHistogram
  subtraction order, f32-ulp different from direct accumulation).
  Never emits a crumb section — a 4bit matrix stays byte-for-byte
  what r18 shipped;
* ``2bit``: requires ``max_bin <= 4`` (config-level hard error), same
  strictness shape as 4bit one tier down: a single feature too wide
  for a crumb is a hard error, a too-wide EFB bundle warns and falls
  back to the nibble (or byte) section;
* ``auto``: adaptive precision — crumb-narrow groups pack four per
  byte, nibble-narrow groups two per byte, wide groups stay
  byte-wide (the three-section layout).  Mixed-width datasets get
  exactly the savings their narrow features earn.

Trees are byte-identical across modes: packing changes the STORAGE of
bin indices, never their values, bundling is identical in every mode,
and the grower/partition/split layers stay bin-index-native (pinned
by tests/test_compact_bins.py on the interpret seam).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .utils.log import Log

#: bins-per-group bound for a nibble-packed group
NIBBLE_MAX_BIN = 16

#: bins-per-group bound for a crumb-packed (2-bit) group
CRUMB_MAX_BIN = 4

_MODES = ("auto", "8bit", "4bit", "2bit")


def resolve_bin_packing(config) -> str:
    """Normalize ``Config.bin_packing`` to one of
    ``auto|8bit|4bit|2bit`` (``None`` config — e.g. legacy cache
    restore — resolves 8bit)."""
    if config is None:
        return "8bit"
    spec = str(config.bin_packing).lower() if hasattr(config,
                                                      "bin_packing") \
        else "8bit"
    if spec not in _MODES:
        Log.warning(f"unknown bin_packing={spec!r}; using '8bit'")
        return "8bit"
    return spec


# ---------------------------------------------------------------------------
# the static pack spec: both section counts in one int.  A crumb-free
# spec equals the plain packed-group count, so every legacy call site
# (and every compiled-function cache key) is numerically unchanged.
# ---------------------------------------------------------------------------
def pack_spec(packed_groups: int, crumb_groups: int = 0) -> int:
    """Encode (P total sub-byte groups, C crumb groups) as one static
    int.  ``crumb_groups == 0`` round-trips to plain ``packed_groups``."""
    return int(packed_groups) | (int(crumb_groups) << 16)


def spec_packed(spec: int) -> int:
    """P: total sub-byte (crumb + nibble) group count of a spec."""
    return int(spec) & 0xFFFF


def spec_crumb(spec: int) -> int:
    """C: crumb (2-bit) group count of a spec (0 for legacy specs)."""
    return int(spec) >> 16


def packed_bytes(spec: int) -> int:
    """Storage bytes of the packed section: ``ceil(C/4)`` crumb bytes
    + ``ceil((P-C)/2)`` nibble bytes.  Accepts a plain group count
    (crumb-free spec) and then matches the legacy two-per-byte math."""
    P, C = spec_packed(spec), spec_crumb(spec)
    return (C + 3) // 4 + (P - C + 1) // 2


def storage_cols(num_groups: int, spec: int) -> int:
    """Total storage byte columns for ``num_groups`` logical groups of
    which the first ``spec_packed(spec)`` are sub-byte packed."""
    return packed_bytes(spec) + (num_groups - spec_packed(spec))


def logical_groups(cols: int, spec: int) -> int:
    """Inverse of :func:`storage_cols` — logical G from storage width."""
    return cols - packed_bytes(spec) + spec_packed(spec)


class BinLayout:
    """Resolved packing layout of one dataset's bin matrix.

    A dataset whose matrix has NO packed section carries
    ``bin_layout = None`` instead (the storage is then the plain
    logical ``(N, G)`` matrix and every consumer takes its legacy
    path untouched)."""

    __slots__ = ("mode", "num_groups", "packed_groups", "crumb_groups")

    def __init__(self, mode: str, num_groups: int, packed_groups: int,
                 crumb_groups: int = 0):
        if not (0 < packed_groups <= num_groups):
            raise ValueError(
                f"BinLayout needs 0 < packed_groups ({packed_groups}) "
                f"<= num_groups ({num_groups}); use bin_layout=None "
                "for an unpacked matrix")
        if not (0 <= crumb_groups <= packed_groups):
            raise ValueError(
                f"BinLayout needs 0 <= crumb_groups ({crumb_groups}) "
                f"<= packed_groups ({packed_groups})")
        self.mode = mode
        self.num_groups = int(num_groups)
        self.packed_groups = int(packed_groups)
        self.crumb_groups = int(crumb_groups)

    # ------------------------------------------------------------------
    @property
    def device_spec(self) -> int:
        """The static pack spec the device kernels carry (equals the
        plain ``packed_groups`` count when the layout has no crumbs)."""
        return pack_spec(self.packed_groups, self.crumb_groups)

    @property
    def crumb_bytes(self) -> int:
        return (self.crumb_groups + 3) // 4

    @property
    def packed_bytes(self) -> int:
        return packed_bytes(self.device_spec)

    @property
    def cols(self) -> int:
        return storage_cols(self.num_groups, self.device_spec)

    def byte_of(self, g: int) -> int:
        if g < self.crumb_groups:
            return g // 4
        if g < self.packed_groups:
            return self.crumb_bytes + (g - self.crumb_groups) // 2
        return self.packed_bytes + (g - self.packed_groups)

    def shift_of(self, g: int) -> int:
        if g < self.crumb_groups:
            return 2 * (g % 4)
        if g < self.packed_groups:
            return 4 * ((g - self.crumb_groups) % 2)
        return 0

    def width_mask(self, g: int) -> int:
        if g < self.crumb_groups:
            return 0x3
        return 0xF if g < self.packed_groups else 0xFF

    def __repr__(self):
        return (f"BinLayout({self.mode}, groups={self.num_groups}, "
                f"packed={self.packed_groups}, "
                f"crumb={self.crumb_groups}, cols={self.cols})")

    # ------------------------------------------------------------------
    def to_state(self) -> dict:
        """Cache-header form (binary cache v3/v4 / shard manifest)."""
        state = {"mode": self.mode, "num_groups": int(self.num_groups),
                 "packed_groups": int(self.packed_groups)}
        if self.crumb_groups:
            # only crumb-carrying layouts grow the key: a crumb-free
            # state dict stays byte-identical to what r18 caches hold
            # (shard manifests compare layout states by dict equality)
            state["crumb_groups"] = int(self.crumb_groups)
        return state

    @classmethod
    def from_state(cls, state: Optional[dict]) -> Optional["BinLayout"]:
        if not state or not int(state.get("packed_groups", 0)):
            return None
        return cls(str(state.get("mode", "auto")),
                   int(state["num_groups"]), int(state["packed_groups"]),
                   int(state.get("crumb_groups", 0)))

    # ------------------------------------------------------------------
    # host-side pack / unpack (vectorized numpy; the native
    # ``ltpu_pack_nibbles`` kernel takes the nibble-only pack when
    # available — it predates crumbs, so a crumb section forces numpy)
    # ------------------------------------------------------------------
    def pack_rows(self, logical: np.ndarray, out: Optional[np.ndarray]
                  = None, lib=None) -> np.ndarray:
        """(n, G) logical uint8 -> (n, cols) storage.  ``out`` writes in
        place (the construction pipeline packs chunk scratch straight
        into the resident storage matrix)."""
        logical = np.ascontiguousarray(logical, dtype=np.uint8)
        n = logical.shape[0]
        if logical.shape[1] != self.num_groups:
            raise ValueError(f"pack_rows expects {self.num_groups} "
                             f"group columns, got {logical.shape[1]}")
        if out is None:
            out = np.empty((n, self.cols), dtype=np.uint8)
        P, C = self.packed_groups, self.crumb_groups
        Cb, Pb = self.crumb_bytes, self.packed_bytes
        if (C == 0 and lib is not None and n
                and _native_pack(lib, logical, P, out)):
            return out
        # crumb section: group 4j+k lands at bit 2k of byte j.  The
        # plane-0 assignment zeroes the upper bits (crumb values are
        # <= 3), so the OR planes need no pre-clear.
        if C:
            out[:, :Cb] = logical[:, 0:C:4]
            for k in (1, 2, 3):
                plane = logical[:, k:C:4]
                if plane.shape[1]:
                    out[:, :plane.shape[1]] |= plane << np.uint8(2 * k)
        lo = logical[:, C:P:2]
        hi = logical[:, C + 1:P:2]
        out[:, Cb:Cb + lo.shape[1]] = lo
        out[:, Cb:Cb + hi.shape[1]] |= hi << np.uint8(4)
        if hi.shape[1] < lo.shape[1]:   # odd nibble count: top nibble
            out[:, Pb - 1] &= np.uint8(0x0F)  # of the last byte stays 0
        out[:, Pb:] = logical[:, P:]
        return out

    def unpack_rows(self, storage: np.ndarray) -> np.ndarray:
        """(n, cols) storage -> (n, G) logical uint8 (a fresh array)."""
        storage = np.asarray(storage, dtype=np.uint8)
        if storage.shape[1] != self.cols:
            raise ValueError(f"unpack_rows expects {self.cols} storage "
                             f"columns, got {storage.shape[1]}")
        n = storage.shape[0]
        P, C = self.packed_groups, self.crumb_groups
        Cb, Pb = self.crumb_bytes, self.packed_bytes
        logical = np.empty((n, self.num_groups), dtype=np.uint8)
        if C:
            ck = storage[:, :Cb]
            for k in range(4):
                cnt = (C - k + 3) // 4
                if cnt > 0:
                    logical[:, k:C:4] = \
                        ((ck >> np.uint8(2 * k)) & np.uint8(0x03))[:, :cnt]
        pk = storage[:, Cb:Pb]
        logical[:, C:P:2] = (pk & np.uint8(0x0F))[:, :(P - C + 1) // 2]
        logical[:, C + 1:P:2] = (pk >> np.uint8(4))[:, :(P - C) // 2]
        logical[:, P:] = storage[:, Pb:]
        return logical

    def unpack_group(self, storage: np.ndarray, g: int) -> np.ndarray:
        """One logical group column's bin values, (n,) uint8."""
        b, sh = self.byte_of(g), self.shift_of(g)
        col = np.asarray(storage[:, b], dtype=np.uint8)
        if g < self.packed_groups:
            return (col >> np.uint8(sh)) & np.uint8(self.width_mask(g))
        return col

    def write_group(self, storage: np.ndarray, g: int,
                    values: np.ndarray, rows=None) -> None:
        """Read-modify-write one group's bin values into its crumb /
        nibble (or byte) — the sparse/CSR push write.  Caller must keep
        each storage BYTE single-writer (up to four packed groups share
        one)."""
        b, sh = self.byte_of(g), self.shift_of(g)
        vals = np.asarray(values, dtype=np.uint8)
        if g >= self.packed_groups:
            if rows is None:
                storage[:, b] = vals
            else:
                storage[rows, b] = vals
            return
        keep = np.uint8(0xFF ^ (self.width_mask(g) << sh))
        if rows is None:
            storage[:, b] = (storage[:, b] & keep) | (vals << np.uint8(sh))
        else:
            cur = storage[rows, b]
            storage[rows, b] = (cur & keep) | (vals << np.uint8(sh))

    def fill_group(self, storage: np.ndarray, g: int, value: int) -> None:
        """Fill one group's crumb/nibble/byte across every row (prefill
        of implicit-zero bins for the streaming CSR push protocol) —
        scalar broadcast, no N-element temp."""
        b, sh = self.byte_of(g), self.shift_of(g)
        v = np.uint8(value)
        if g >= self.packed_groups:
            storage[:, b] = v
            return
        keep = np.uint8(0xFF ^ (self.width_mask(g) << sh))
        storage[:, b] &= keep
        storage[:, b] |= np.uint8(v << sh)


def _native_pack(lib, logical: np.ndarray, packed_groups: int,
                 out: np.ndarray) -> bool:
    """Native nibble pack (``ltpu_pack_nibbles``); False -> numpy path
    (a strided buffer).  Nibble-only: callers must not reach here with
    a crumb section."""
    import ctypes
    if not (logical.flags.c_contiguous and out.flags.c_contiguous):
        return False
    n, g = logical.shape
    lib.ltpu_pack_nibbles(
        logical.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        n, g, packed_groups,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        out.shape[1])
    return True


# ---------------------------------------------------------------------------
# layout construction (called from Dataset._build_groups once bundles
# and per-group bin counts are known)
# ---------------------------------------------------------------------------
def build_layout(mode: str, group_num_bin: Sequence[int],
                 group_features: Optional[List[List[int]]] = None,
                 feature_names: Optional[Sequence[str]] = None
                 ) -> Optional[BinLayout]:
    """Resolve the layout for a group list ALREADY ordered
    narrowest-first.  ``mode`` is the resolved ``bin_packing``; returns
    None when nothing packs (8bit mode, or auto with no narrow group).

    ``4bit`` strictness: a wide SINGLE-FEATURE group is a hard error
    naming the group and its feature (it means max_bin > 16 reached
    construction — a silently-wide "4-bit" matrix would defeat the
    capacity math the caller asked for).  A wide multi-feature EFB
    bundle only warns: it keeps its 8-bit-identical membership and
    stores byte-wide, preserving byte-exact tree parity (see the
    module docstring).  ``2bit`` applies the same shape one tier down
    against :data:`CRUMB_MAX_BIN`, with too-wide EFB bundles falling
    back to the nibble (or byte) section.

    Only ``auto`` and ``2bit`` emit a crumb section — ``4bit``
    matrices stay byte-for-byte what r18 shipped."""
    G = len(group_num_bin)
    if mode == "8bit" or G == 0:
        return None

    def _label(g: int) -> str:
        feats = group_features[g] if group_features else []
        labels = [feature_names[f] if feature_names
                  and f < len(feature_names) else f"feature {f}"
                  for f in feats]
        names = (f" (features: {', '.join(map(str, labels))})"
                 if labels else "")
        return (f"group {g} ({group_num_bin[g]} bins){names}")

    def _split_wide(lo: int, bound: int):
        """(single-feature, multi-feature) groups in ``lo..G`` whose
        bin count exceeds ``bound`` — EVERY wide group is inspected,
        not just the widest: a wide single-feature group is a hard
        error even when an even wider EFB bundle exists beside it."""
        wide = [g for g in range(lo, G) if group_num_bin[g] > bound]
        single = [g for g in wide if not group_features
                  or len(group_features[g]) == 1]
        return single, [g for g in wide if g not in single]

    C = 0
    if mode in ("auto", "2bit"):
        while C < G and group_num_bin[C] <= CRUMB_MAX_BIN:
            C += 1
    P = C
    while P < G and group_num_bin[P] <= NIBBLE_MAX_BIN:
        P += 1
    if mode == "2bit" and C < G:
        wide_single, wide_multi = _split_wide(C, CRUMB_MAX_BIN)
        if wide_multi:
            Log.warning(
                "bin_packing=2bit: EFB bundle(s) wider than the "
                f"{CRUMB_MAX_BIN} bins a crumb holds — "
                + "; ".join(_label(g) for g in wide_multi)
                + " — each bundle keeps its layout and stores nibble- "
                "or byte-wide (three-section matrix) so trees stay "
                "byte-identical to the 8-bit path; disable "
                "enable_bundle for a fully crumb-packed matrix")
        if wide_single:
            # a categorical feature can exceed max_bin even when
            # max_bin <= 4 (its bin count follows the fitted category
            # table), so "lower max_bin" is not always the way out
            Log.fatal(
                "bin_packing=2bit: feature group(s) too wide for the "
                f"{CRUMB_MAX_BIN} bins a crumb holds — "
                + "; ".join(_label(g) for g in wide_single)
                + " — lower max_bin (<= 4; a categorical feature "
                "needs <= 3 distinct categories) or use "
                "bin_packing=auto to keep wide groups nibble- or "
                "byte-wide")
    if mode in ("4bit", "2bit") and P < G:
        wide_single, wide_multi = _split_wide(P, NIBBLE_MAX_BIN)
        if wide_multi:
            Log.warning(
                f"bin_packing={mode}: EFB bundle(s) wider than the "
                f"{NIBBLE_MAX_BIN} bins a nibble holds — "
                + "; ".join(_label(g) for g in wide_multi)
                + " — each bundle keeps its layout and stores "
                "byte-wide (two-section matrix) so trees stay "
                "byte-identical to the 8-bit path; disable "
                "enable_bundle for a fully packed matrix")
        if wide_single:
            # a categorical feature can exceed max_bin even when
            # max_bin <= 16 (its bin count follows the fitted category
            # table), so "lower max_bin" is not always the way out
            Log.fatal(
                f"bin_packing={mode}: feature group(s) too wide for "
                f"the {NIBBLE_MAX_BIN} bins a nibble holds — "
                + "; ".join(_label(g) for g in wide_single)
                + " — lower max_bin (<= 16; a categorical feature "
                "needs <= 15 distinct categories) or use "
                "bin_packing=auto to keep wide groups byte-wide")
    if P == 0:
        return None
    return BinLayout(mode, G, P, C)
