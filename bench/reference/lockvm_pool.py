"""Plain reference for lockVM cells over a pool of locks, in Python.

The benchmark's yardstick for ``correct`` in cells whose threads pick a
random lock from a pool every iteration (the TWA paper's Figure 2). It
imports nothing from the program under test (``src/repro``) and nothing from
the single-lock reference beside it: the ISA encoding, the cost model, the
lock programs, the MutexBench loop, the fault-schedule draw and a
sequential interpreter are all written out here, so that a change to the
program cannot move the reference with it.

What it states (the guarantees of every lockVM configuration, and those of
a pool):

* each cell runs the named lock under MutexBench's loop
  ``{pick; acquire; CS; release; NCS}`` with the configuration's cost
  model; with ``n_locks`` above 1 each iteration first draws the lock's
  index from the thread's PRNG (``PRNG R_LIDX, n_locks``) and its region
  (``MULI R_LOCK, R_LIDX, LOCK_STRIDE``);
* memory holds the ``n_locks`` lock regions, then the threads' queue
  nodes, then one waiting array shared by every lock (``HASH`` of the
  ticket with the lock's region) or, with ``private_arrays``, one array
  per lock (``HASHP`` of the ticket into the array of ``R_LIDX``);
* each lock's exclusion and handover hold on their own: a release stamps
  its own lock's time, and a waited acquisition of that lock reads it;
* a plain store becomes visible at its commit event, ``issue + cost``
  cycles later; an atomic is visible at once; every thread parked on an
  address wakes at each committed write to it;
* events execute in one total order: the earliest pending commit or thread
  operation first, a commit before a thread operation at equal time, the
  lowest thread index first within each; a scheduled fault applies before
  the event whose index it names;
* every statistic is exact: integer, wrapped to int32 as the machine word.

``run_cell`` returns the per-cell numbers that ``repro.sim.run_sweep``
reports for the same cell. Two mutations make it the control that the
comparison has to reject: ``eager_store`` breaks the delayed-visibility
guarantee, and ``shared_hash`` hashes the private-array arm into the first
array, as if the locks shared it.
"""

from __future__ import annotations

import math

import numpy as np

# --- ISA encoding -----------------------------------------------------------
(NOP, LOAD, STORE, STOREI, FADD, SWAP, CASZ, ADDI, MOVI, MOV, SUB, MULI, ANDI,
 HASH, HASHP, BEQ, BNE, BLE, BGT, BEQI, BNEI, BLEI, BGTI, JMP, WORKI, WORKR,
 PRNG, SPIN_EQ, SPIN_NE, SPIN_EQI, SPIN_NEI, ACQ, REL, HALT, SPIN_GE,
 TSTART) = range(36)

R_TID, R_NODE, R_LOCK, R_LIDX = 0, 1, 2, 3
R_TX, R_G, R_DX, R_AT = 4, 5, 6, 7
R_U, R_V, R_K, R_W = 8, 9, 10, 11
R_T1, R_T2, R_NX, R_Z = 12, 13, 14, 15
N_REGS = 16

WORDS_PER_SECTOR = 16
LINE_SHIFT = 4
OFF_TICKET, OFF_GRANT, OFF_LGRANT, OFF_TAIL, OFF_PGRANTS = 0, 16, 32, 48, 64
OFF_RD = OFF_PGRANTS
LOCK_STRIDE = 64 + 16 * WORDS_PER_SECTOR
MCS_FLAG, MCS_NEXT, MCS_NODE_STRIDE = 0, 16, 32

INF = 1 << 29
N_LAT_BUCKETS = 32
WORK_SCALE = 8  # cycles per PRNG step of CS/NCS work

# cost vector order: local, hit, miss, xfer, store owned, store shared,
# invalidation per sharer, atomic extra, wake
COST_KEYS = ("C_LOCAL", "C_HIT", "C_MISS", "C_XFER", "C_STORE_OWNED",
             "C_STORE_SHARED", "C_INV", "C_ATOMIC", "C_WAKE")
I_LOCAL, I_HIT, I_MISS, I_XFER, I_ST_OWNED, I_ST_SHARED, I_INV, I_ATOMIC, \
    I_WAKE = range(9)

F_NONE, F_PREEMPT, F_SPURIOUS, F_ABORT = 0, 1, 2, 3


def _w32(x: int) -> int:
    x &= 0xFFFFFFFF
    return x - 0x100000000 if x >= 0x80000000 else x


# --- memory layout ----------------------------------------------------------
def _round_sector(w: int) -> int:
    return (w + WORDS_PER_SECTOR - 1) // WORDS_PER_SECTOR * WORDS_PER_SECTOR


class Layout:
    """``n_locks`` lock regions, the threads' nodes, then one waiting array
    shared by every lock, or one per lock with ``private_arrays``."""

    def __init__(self, n_threads: int, wa_size: int, *, n_locks=1,
                 private_arrays=False, long_term_threshold=1, sem_permits=4,
                 reader_fraction=50):
        self.n_threads = n_threads
        self.wa_size = wa_size
        self.n_locks = n_locks
        self.private_arrays = private_arrays
        self.long_term_threshold = long_term_threshold
        self.sem_permits = sem_permits
        self.reader_fraction = reader_fraction
        self.node_base = n_locks * LOCK_STRIDE
        self.wa_base = _round_sector(self.node_base
                                     + n_threads * MCS_NODE_STRIDE)
        n_arrays = n_locks if private_arrays else 1
        self.mem_words = _round_sector(self.wa_base + wa_size * n_arrays)


class Asm:
    def __init__(self):
        self.rows, self.labels, self.fixups = [], {}, []

    def label(self, name):
        self.labels[name] = len(self.rows)

    def emit(self, op, a=0, b=0, c=0, imm=0):
        if isinstance(imm, str):
            self.fixups.append((len(self.rows), imm))
            imm = -1
        self.rows.append([op, a, b, c, imm])

    def finish(self):
        for row, name in self.fixups:
            self.rows[row][4] = self.labels[name]
        return [tuple(r) for r in self.rows]


# --- lock programs ----------------------------------------------------------
def _hash(asm, dst, src, L):
    """The waiting-array slot of ticket ``src``: in the shared array, mixed
    with the lock's region; in a private array, in that of lock ``R_LIDX``."""
    if L.private_arrays:
        asm.emit(HASHP, dst, src, R_LIDX)
    else:
        asm.emit(HASH, dst, src, R_LOCK)


def _fast_tail(asm, tag):
    asm.emit(ACQ, R_LIDX, 0, 1)
    asm.emit(JMP, 0, 0, 0, f"{tag}_in")
    asm.label(f"{tag}_fast")
    asm.emit(ACQ, R_LIDX, 0, 0)
    asm.label(f"{tag}_in")


def _ticket_acq(asm, tag, L):
    asm.emit(FADD, R_TX, R_LOCK, 1, OFF_TICKET)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(BEQ, R_TX, R_G, 0, f"{tag}_fast")
    asm.emit(SPIN_EQ, R_TX, R_LOCK, 0, OFF_GRANT)
    _fast_tail(asm, tag)


def _ticket_rel(asm, tag, L):
    asm.emit(ADDI, R_K, R_TX, 0, 1)
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STORE, R_LOCK, R_K, 0, OFF_GRANT)


def _twa_wait(asm, tag, L, fast_label=None):
    thr = L.long_term_threshold
    asm.emit(FADD, R_TX, R_LOCK, 1, OFF_TICKET)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    if fast_label is not None:
        asm.emit(BEQI, R_DX, 0, 0, fast_label)
    asm.emit(BLEI, R_DX, 0, thr, f"{tag}_st")
    _hash(asm, R_AT, R_TX, L)
    asm.label(f"{tag}_lt")
    asm.emit(LOAD, R_U, R_AT, 0, 0)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    asm.emit(BLEI, R_DX, 0, thr, f"{tag}_st")
    asm.emit(SPIN_NE, R_U, R_AT, 0, 0)
    asm.emit(JMP, 0, 0, 0, f"{tag}_lt")
    asm.label(f"{tag}_st")
    asm.emit(SPIN_EQ, R_TX, R_LOCK, 0, OFF_GRANT)


def _twa_pass(asm, tag, L, rel=False, restore_z=True):
    asm.emit(ADDI, R_K, R_TX, 0, 1)
    if rel:
        asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STORE, R_LOCK, R_K, 0, OFF_GRANT)
    asm.emit(ADDI, R_T1, R_K, 0, L.long_term_threshold)
    _hash(asm, R_AT, R_T1, L)
    asm.emit(FADD, R_Z, R_AT, 1, 0)
    if restore_z:
        asm.emit(MOVI, R_Z, 0, 0, 0)


def _twa_acq(asm, tag, L):
    _twa_wait(asm, tag, L, fast_label=f"{tag}_fast")
    _fast_tail(asm, tag)


def _twa_rel(asm, tag, L):
    _twa_pass(asm, tag, L, rel=True, restore_z=False)


def _mcs_acq(asm, tag, L):
    asm.emit(STOREI, R_NODE, 1, 0, MCS_FLAG)
    asm.emit(STOREI, R_NODE, 0, 0, MCS_NEXT)
    asm.emit(SWAP, R_T1, R_LOCK, R_NODE, OFF_TAIL)
    asm.emit(BEQI, R_T1, 0, 0, f"{tag}_fast")
    asm.emit(STORE, R_T1, R_NODE, 0, MCS_NEXT)
    asm.emit(SPIN_EQI, 0, R_NODE, 0, MCS_FLAG)
    _fast_tail(asm, tag)


def _mcs_rel(asm, tag, L):
    asm.emit(LOAD, R_NX, R_NODE, 0, MCS_NEXT)
    asm.emit(BNEI, R_NX, 0, 0, f"{tag}_succ")
    asm.emit(CASZ, R_T1, R_LOCK, R_NODE, OFF_TAIL)
    asm.emit(BEQ, R_T1, R_NODE, 0, f"{tag}_done")
    asm.emit(SPIN_NEI, 0, R_NODE, 0, MCS_NEXT)
    asm.emit(LOAD, R_NX, R_NODE, 0, MCS_NEXT)
    asm.label(f"{tag}_succ")
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STORE, R_NX, R_Z, 0, MCS_FLAG)
    asm.label(f"{tag}_done")


def _tkt_dual_acq(asm, tag, L):
    thr = L.long_term_threshold
    asm.emit(FADD, R_TX, R_LOCK, 1, OFF_TICKET)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    asm.emit(BEQI, R_DX, 0, 0, f"{tag}_fast")
    asm.emit(BLEI, R_DX, 0, thr, f"{tag}_st")
    asm.label(f"{tag}_lt")
    asm.emit(LOAD, R_U, R_LOCK, 0, OFF_LGRANT)
    asm.emit(SUB, R_DX, R_TX, R_U)
    asm.emit(BLEI, R_DX, 0, thr, f"{tag}_st")
    asm.emit(SPIN_NE, R_U, R_LOCK, 0, OFF_LGRANT)
    asm.emit(JMP, 0, 0, 0, f"{tag}_lt")
    asm.label(f"{tag}_st")
    asm.emit(SPIN_EQ, R_TX, R_LOCK, 0, OFF_GRANT)
    _fast_tail(asm, tag)


def _tkt_dual_rel(asm, tag, L):
    asm.emit(ADDI, R_K, R_TX, 0, 1)
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STORE, R_LOCK, R_K, 0, OFF_GRANT)
    asm.emit(STORE, R_LOCK, R_K, 0, OFF_LGRANT)


def _twa_id_acq(asm, tag, L):
    thr = L.long_term_threshold
    asm.emit(FADD, R_TX, R_LOCK, 1, OFF_TICKET)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    asm.emit(BEQI, R_DX, 0, 0, f"{tag}_fast")
    asm.emit(BLEI, R_DX, 0, thr, f"{tag}_st")
    _hash(asm, R_AT, R_TX, L)
    asm.emit(STORE, R_AT, R_T2, 0, 0)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    asm.emit(BLEI, R_DX, 0, thr, f"{tag}_st")
    asm.emit(SPIN_NE, R_T2, R_AT, 0, 0)
    asm.label(f"{tag}_st")
    asm.emit(SPIN_EQ, R_TX, R_LOCK, 0, OFF_GRANT)
    _fast_tail(asm, tag)


def _twa_id_rel(asm, tag, L):
    asm.emit(ADDI, R_K, R_TX, 0, 1)
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STORE, R_LOCK, R_K, 0, OFF_GRANT)
    asm.emit(ADDI, R_T1, R_K, 0, L.long_term_threshold)
    _hash(asm, R_AT, R_T1, L)
    asm.emit(STORE, R_AT, R_Z, 0, 0)


def _twa_staged_acq(asm, tag, L):
    asm.emit(FADD, R_TX, R_LOCK, 1, OFF_TICKET)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    asm.emit(BEQI, R_DX, 0, 0, f"{tag}_fast")
    asm.emit(BLEI, R_DX, 0, 1, f"{tag}_c")
    asm.emit(BLEI, R_DX, 0, 2, f"{tag}_b")
    _hash(asm, R_AT, R_TX, L)
    asm.label(f"{tag}_lt")
    asm.emit(LOAD, R_U, R_AT, 0, 0)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    asm.emit(BLEI, R_DX, 0, 2, f"{tag}_b")
    asm.emit(SPIN_NE, R_U, R_AT, 0, 0)
    asm.emit(JMP, 0, 0, 0, f"{tag}_lt")
    asm.label(f"{tag}_b")
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    asm.emit(BLEI, R_DX, 0, 1, f"{tag}_promote")
    asm.emit(SPIN_NE, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(JMP, 0, 0, 0, f"{tag}_b")
    asm.label(f"{tag}_promote")
    asm.emit(ADDI, R_T1, R_TX, 0, 1)
    _hash(asm, R_AT, R_T1, L)
    asm.emit(FADD, R_Z, R_AT, 1, 0)
    asm.emit(MOVI, R_Z, 0, 0, 0)
    asm.label(f"{tag}_c")
    asm.emit(SPIN_EQ, R_TX, R_LOCK, 0, OFF_GRANT)
    _fast_tail(asm, tag)


def _add(asm, dst, src_a, src_b):
    asm.emit(SUB, R_V, R_Z, src_b)
    asm.emit(SUB, dst, src_a, R_V)


def _partitioned_acq(asm, tag, L):
    asm.emit(FADD, R_TX, R_LOCK, 1, OFF_TICKET)
    asm.emit(ANDI, R_T1, R_TX, 0, 15)
    asm.emit(MULI, R_T1, R_T1, 0, WORDS_PER_SECTOR)
    _add(asm, R_AT, R_LOCK, R_T1)
    asm.emit(LOAD, R_G, R_AT, 0, OFF_PGRANTS)
    asm.emit(BEQ, R_G, R_TX, 0, f"{tag}_fast")
    asm.emit(SPIN_EQ, R_TX, R_AT, 0, OFF_PGRANTS)
    _fast_tail(asm, tag)


def _partitioned_rel(asm, tag, L):
    asm.emit(ADDI, R_K, R_TX, 0, 1)
    asm.emit(ANDI, R_T1, R_K, 0, 15)
    asm.emit(MULI, R_T1, R_T1, 0, WORDS_PER_SECTOR)
    _add(asm, R_AT, R_LOCK, R_T1)
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STORE, R_AT, R_K, 0, OFF_PGRANTS)


def _anderson_acq(asm, tag, L):
    asm.emit(FADD, R_TX, R_LOCK, 1, OFF_TICKET)
    _hash(asm, R_AT, R_TX, L)
    asm.emit(LOAD, R_U, R_AT, 0, 0)
    asm.emit(BNEI, R_U, 0, 0, f"{tag}_fast")
    asm.emit(SPIN_NEI, 0, R_AT, 0, 0)
    asm.emit(STOREI, R_AT, 0, 0, 0)
    asm.emit(ACQ, R_LIDX, 0, 1)
    asm.emit(JMP, 0, 0, 0, f"{tag}_in")
    asm.label(f"{tag}_fast")
    asm.emit(STOREI, R_AT, 0, 0, 0)
    asm.emit(ACQ, R_LIDX, 0, 0)
    asm.label(f"{tag}_in")


def _anderson_rel(asm, tag, L):
    asm.emit(ADDI, R_K, R_TX, 0, 1)
    _hash(asm, R_AT, R_K, L)
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STOREI, R_AT, 1, 0, 0)


def _clh_acq(asm, tag, L):
    asm.emit(STOREI, R_NODE, 1, 0, MCS_FLAG)
    asm.emit(SWAP, R_T1, R_LOCK, R_NODE, OFF_TAIL)
    asm.emit(LOAD, R_U, R_T1, 0, MCS_FLAG)
    asm.emit(BEQI, R_U, 0, 0, f"{tag}_fast")
    asm.emit(SPIN_EQI, 0, R_T1, 0, MCS_FLAG)
    _fast_tail(asm, tag)


def _clh_rel(asm, tag, L):
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STOREI, R_NODE, 0, 0, MCS_FLAG)
    asm.emit(MOV, R_NODE, R_T1)


def _hemlock_acq(asm, tag, L):
    asm.emit(SWAP, R_T1, R_LOCK, R_NODE, OFF_TAIL)
    asm.emit(BEQI, R_T1, 0, 0, f"{tag}_fast")
    asm.emit(ADDI, R_V, R_LOCK, 0, 1)
    asm.emit(SPIN_EQ, R_V, R_T1, 0, MCS_FLAG)
    asm.emit(STOREI, R_T1, 0, 0, MCS_FLAG)
    _fast_tail(asm, tag)


def _hemlock_rel(asm, tag, L):
    asm.emit(CASZ, R_T1, R_LOCK, R_NODE, OFF_TAIL)
    asm.emit(BEQ, R_T1, R_NODE, 0, f"{tag}_done")
    asm.emit(ADDI, R_V, R_LOCK, 0, 1)
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STORE, R_NODE, R_V, 0, MCS_FLAG)
    asm.emit(SPIN_EQI, 0, R_NODE, 0, MCS_FLAG)
    asm.label(f"{tag}_done")


def _twa_sem_acq(asm, tag, L):
    K, thr = L.sem_permits, L.long_term_threshold
    asm.emit(FADD, R_TX, R_LOCK, 1, OFF_TICKET)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    asm.emit(BLEI, R_DX, 0, K - 1, f"{tag}_fast")
    asm.emit(BLEI, R_DX, 0, K - 1 + thr, f"{tag}_st")
    _hash(asm, R_AT, R_TX, L)
    asm.label(f"{tag}_lt")
    asm.emit(LOAD, R_U, R_AT, 0, 0)
    asm.emit(LOAD, R_G, R_LOCK, 0, OFF_GRANT)
    asm.emit(SUB, R_DX, R_TX, R_G)
    asm.emit(BLEI, R_DX, 0, K - 1 + thr, f"{tag}_st")
    asm.emit(SPIN_NE, R_U, R_AT, 0, 0)
    asm.emit(JMP, 0, 0, 0, f"{tag}_lt")
    asm.label(f"{tag}_st")
    asm.emit(ADDI, R_T1, R_TX, 0, -(K - 1))
    asm.emit(SPIN_GE, R_T1, R_LOCK, 0, OFF_GRANT)
    _fast_tail(asm, tag)


def _twa_sem_rel(asm, tag, L):
    K, thr = L.sem_permits, L.long_term_threshold
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(FADD, R_K, R_LOCK, 1, OFF_GRANT)
    asm.emit(ADDI, R_T1, R_K, 0, K + thr)
    _hash(asm, R_AT, R_T1, L)
    asm.emit(FADD, R_Z, R_AT, 1, 0)
    asm.emit(MOVI, R_Z, 0, 0, 0)


def _fissile_twa_acq(asm, tag, L):
    asm.emit(MOVI, R_V, 0, 0, 0)
    asm.emit(SWAP, R_T1, R_LOCK, R_T2, OFF_TAIL)
    asm.emit(BEQI, R_T1, 0, 0, f"{tag}_fast")
    asm.emit(MOVI, R_V, 0, 0, 1)
    _twa_wait(asm, tag, L)
    asm.label(f"{tag}_tas")
    asm.emit(SWAP, R_T1, R_LOCK, R_T2, OFF_TAIL)
    asm.emit(BEQI, R_T1, 0, 0, f"{tag}_got")
    asm.emit(SPIN_EQI, 0, R_LOCK, 0, OFF_TAIL)
    asm.emit(JMP, 0, 0, 0, f"{tag}_tas")
    asm.label(f"{tag}_got")
    _fast_tail(asm, tag)


def _fissile_twa_rel(asm, tag, L):
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(STOREI, R_LOCK, 0, 0, OFF_TAIL)
    asm.emit(BEQI, R_V, 0, 0, f"{tag}_out")
    _twa_pass(asm, tag, L)
    asm.label(f"{tag}_out")


def _twa_rw_acq(asm, tag, L):
    asm.emit(MOVI, R_V, 0, 0, 1)
    asm.emit(PRNG, R_T2, 0, 0, 100)
    asm.emit(BGTI, R_T2, 0, L.reader_fraction - 1, f"{tag}_entry")
    asm.emit(MOVI, R_V, 0, 0, 0)
    asm.label(f"{tag}_entry")
    _twa_wait(asm, tag, L, fast_label=f"{tag}_fastin")
    asm.emit(BEQI, R_V, 0, 0, f"{tag}_rdw")
    asm.emit(SPIN_EQI, 0, R_LOCK, 0, OFF_RD)
    asm.emit(ACQ, R_LIDX, 0, 1)
    asm.emit(JMP, 0, 0, 0, f"{tag}_in")
    asm.label(f"{tag}_rdw")
    asm.emit(FADD, R_U, R_LOCK, 1, OFF_RD)
    asm.emit(ACQ, R_LIDX, 0, 1)
    asm.emit(JMP, 0, 0, 0, f"{tag}_pass")
    asm.label(f"{tag}_fastin")
    asm.emit(BEQI, R_V, 0, 0, f"{tag}_rdf")
    asm.emit(SPIN_EQI, 0, R_LOCK, 0, OFF_RD)
    asm.emit(ACQ, R_LIDX, 0, 0)
    asm.emit(JMP, 0, 0, 0, f"{tag}_in")
    asm.label(f"{tag}_rdf")
    asm.emit(FADD, R_U, R_LOCK, 1, OFF_RD)
    asm.emit(ACQ, R_LIDX, 0, 0)
    asm.label(f"{tag}_pass")
    _twa_pass(asm, tag, L)
    asm.label(f"{tag}_in")


def _twa_rw_rel(asm, tag, L):
    asm.emit(BEQI, R_V, 0, 0, f"{tag}_rd")
    asm.emit(REL, 0, R_LIDX, 0, 0)
    _twa_pass(asm, tag, L)
    asm.emit(JMP, 0, 0, 0, f"{tag}_out")
    asm.label(f"{tag}_rd")
    asm.emit(REL, 0, R_LIDX, 0, 0)
    asm.emit(FADD, R_U, R_LOCK, -1, OFF_RD)
    asm.label(f"{tag}_out")


LOCKS = {
    "anderson": (_anderson_acq, _anderson_rel),
    "clh": (_clh_acq, _clh_rel),
    "fissile-twa": (_fissile_twa_acq, _fissile_twa_rel),
    "hemlock": (_hemlock_acq, _hemlock_rel),
    "mcs": (_mcs_acq, _mcs_rel),
    "partitioned": (_partitioned_acq, _partitioned_rel),
    "ticket": (_ticket_acq, _ticket_rel),
    "tkt-dual": (_tkt_dual_acq, _tkt_dual_rel),
    "twa": (_twa_acq, _twa_rel),
    "twa-id": (_twa_id_acq, _twa_id_rel),
    "twa-rw": (_twa_rw_acq, _twa_rw_rel),
    "twa-sem": (_twa_sem_acq, _twa_sem_rel),
    "twa-staged": (_twa_staged_acq, _ticket_rel),
}


def build_mutexbench(lock: str, L: Layout, *, cs_work: int, ncs_max: int,
                     collect_latency: bool) -> list[tuple]:
    """MutexBench's loop: pick a lock; acquire; CS; release; NCS."""
    if lock == "anderson" and L.n_locks > 1 and not L.private_arrays:
        # one array of boolean flags shared by several locks would let a
        # slot granted for one lock admit a waiter of another
        raise ValueError("anderson needs private arrays for a pool of locks")
    acquire, release = LOCKS[lock]
    asm = Asm()
    asm.label("top")
    if L.n_locks > 1:
        asm.emit(PRNG, R_LIDX, 0, 0, L.n_locks)
        asm.emit(MULI, R_LOCK, R_LIDX, 0, LOCK_STRIDE)
    if collect_latency:
        asm.emit(TSTART, 0, 0, 0)
    acquire(asm, "a", L)
    if cs_work > 0:
        asm.emit(WORKI, 0, 0, 0, cs_work * WORK_SCALE)
    release(asm, "r", L)
    if ncs_max > 0:
        asm.emit(PRNG, R_W, 0, 0, ncs_max)
        asm.emit(MULI, R_W, R_W, 0, WORK_SCALE)
        asm.emit(WORKR, R_W, 0, 0, 0)
    asm.emit(JMP, 0, 0, 0, "top")
    return asm.finish()


def init_mem(lock: str, L: Layout) -> list[int]:
    mem = [0] * L.mem_words
    for lidx in range(L.n_locks):
        base = lidx * LOCK_STRIDE
        if lock == "anderson":    # each lock's slot of ticket 0 starts granted
            if L.private_arrays:
                mem[L.wa_base + lidx * L.wa_size] = 1
            else:
                mem[L.wa_base + (0 ^ base) % L.wa_size] = 1
        elif lock == "clh":       # each tail starts at a free sentinel cell
            mem[base + OFF_TAIL] = base + OFF_PGRANTS
    return mem


def init_regs(L: Layout) -> list[list[int]]:
    regs = []
    for t in range(L.n_threads):
        r = [0] * N_REGS
        r[R_TID] = t
        r[R_NODE] = L.node_base + t * MCS_NODE_STRIDE
        r[R_T2] = t + 1
        regs.append(r)
    return regs


def fault_schedule(*, seed: int, n_threads: int, preempt_faults: int,
                   preempt_cost: int, max_events: int,
                   fault_evt_span: int | None) -> list[tuple[int, int, int, int]]:
    """A cell's preemption windows, drawn from its own coordinates.

    Rows are ``(kind, event index, thread, stall)`` in event order. The
    draw is the one the lockVM's sweeps define: a NumPy generator seeded
    with ``[0xFA17, seed, threads, preempts, 0, 0]`` picks distinct event
    indices below the span, then the kinds' order, the threads and the
    stall of each.
    """
    if preempt_faults == 0:
        return []
    rng = np.random.default_rng([0xFA17, seed, n_threads, preempt_faults, 0, 0])
    span = max_events if fault_evt_span is None else min(fault_evt_span,
                                                         max_events)
    span = max(span, 1)
    total = min(preempt_faults, span)
    evts = np.sort(rng.choice(span, size=total, replace=False))
    kinds = np.full(total, F_PREEMPT)
    rng.shuffle(kinds)
    tids = rng.integers(0, max(n_threads, 1), size=total)
    args = np.where(kinds == F_PREEMPT,
                    rng.integers(preempt_cost, preempt_cost + 1, size=total),
                    0)
    return [(int(k), int(e), int(t), int(a))
            for k, e, t, a in zip(kinds, evts, tids, args)]


# --- the interpreter --------------------------------------------------------
def _reg(idx: int) -> int:
    """Register read index: one negative wrap, then clamp (as a gather)."""
    if idx < 0:
        idx += N_REGS
    return min(max(idx, 0), N_REGS - 1)


def _set(R: list, idx: int, val: int) -> None:
    """Register write: one negative wrap, then drop if out of range."""
    if idx < 0:
        idx += N_REGS
    if 0 <= idx < N_REGS:
        R[idx] = val


def interpret(prog, *, n_threads: int, n_locks: int, mem: list[int], regs,
              wa_base: int, wa_size: int, horizon: int, max_events: int,
              seed: int, costs: list[int], faults=(),
              mutate: tuple = ()) -> dict:
    """Run one cell, one event at a time, until the horizon or the cap."""
    eager_store = "eager_store" in mutate
    C = costs
    T = n_threads
    wa_mask = wa_size - 1
    fault_at = {e: (k, t, a) for k, e, t, a in faults if k != F_NONE}
    next_time = [0] * T
    pc = [0] * T
    prng = [(seed + t * 2654435761) & 0xFFFFFFFF for t in range(T)]
    sharers = [set() for _ in range(len(mem) // WORDS_PER_SECTOR)]
    dirty = [-1] * len(sharers)
    pend_addr, pend_val, pend_time = [-1] * T, [0] * T, [0] * T
    spin_addr, wake_delay = [-1] * T, [0] * T
    acq, waited_acq, acq_t0 = [0] * T, [0] * T, [-1] * T
    rel_time = [-1] * n_locks             # each lock's last release
    hand_sum = hand_cnt = events = 0
    lat_hist = [0] * N_LAT_BUCKETS

    def load_cost(t, ln):
        if t in sharers[ln]:
            return C[I_HIT]
        d = dirty[ln]
        return C[I_XFER] if (d >= 0 and d != t) else C[I_MISS]

    def store_cost(t, ln, atomic):
        row = sharers[ln]
        others = len(row) - (t in row)
        if t in row and others == 0:
            cost = C[I_ST_OWNED]
        else:
            cost = C[I_ST_SHARED] + C[I_INV] * others
        return cost + (C[I_ATOMIC] if atomic else 0)

    def wake(addr, at):
        for u in range(T):
            if spin_addr[u] == addr:
                next_time[u] = _w32(at + C[I_WAKE] + wake_delay[u])
                wake_delay[u] = 0
                spin_addr[u] = -1

    def select():
        t_cm, tc = INF, 0
        for u in range(T):
            if pend_addr[u] >= 0 and pend_time[u] < t_cm:
                t_cm, tc = pend_time[u], u
        t_th = min(next_time)
        return t_cm, tc, t_th, next_time.index(t_th)

    while True:
        t_cm, tc, t_th, tt = select()
        now = min(t_cm, t_th)
        if not (events < max_events and now < horizon):
            break
        f = fault_at.get(events)
        if f is not None:
            kind, ft, fa = f
            if kind == F_PREEMPT:
                if next_time[ft] < INF:
                    next_time[ft] = _w32(next_time[ft] + fa)
                else:
                    wake_delay[ft] = _w32(wake_delay[ft] + fa)
            elif kind == F_SPURIOUS:
                if spin_addr[ft] >= 0:
                    next_time[ft] = _w32(now + C[I_WAKE] + wake_delay[ft])
                    wake_delay[ft] = 0
                    spin_addr[ft] = -1
            else:
                next_time[ft] = INF
                spin_addr[ft] = -1
            t_cm, tc, t_th, tt = select()
            now = min(t_cm, t_th)
            if now >= horizon:
                continue
        events += 1

        if t_cm <= t_th:                  # a pending store commits
            t = tc
            addr = pend_addr[t]
            ln = addr >> LINE_SHIFT
            mem[addr] = pend_val[t]
            sharers[ln] = {t}
            dirty[ln] = t
            pend_addr[t] = -1
            wake(addr, now)
            continue

        t = tt
        op, a, b, c_, imm = prog[pc[t]]
        R = regs[t]
        ra, rb, rc = R[_reg(a)], R[_reg(b)], R[_reg(c_)]
        new_pc = pc[t] + 1
        cost = C[I_LOCAL]
        sleep = False

        if op == LOAD:
            addr = _w32(rb + imm)
            ln = addr >> LINE_SHIFT
            cost = load_cost(t, ln)
            if t not in sharers[ln] and dirty[ln] >= 0 and dirty[ln] != t:
                dirty[ln] = -1
            _set(R, a, mem[addr])
            sharers[ln].add(t)
        elif op == STORE or op == STOREI:
            addr = _w32(ra + imm)
            val = rb if op == STORE else b
            cost = store_cost(t, addr >> LINE_SHIFT, False)
            pend_addr[t], pend_val[t] = addr, val
            pend_time[t] = _w32(now + cost)
            if eager_store:
                mem[addr] = val
        elif op == FADD or op == SWAP or op == CASZ:
            addr = _w32(rb + imm)
            ln = addr >> LINE_SHIFT
            cost = store_cost(t, ln, True)
            old = mem[addr]
            if op == FADD:
                new = _w32(old + c_)
            elif op == SWAP:
                new = rc
            else:
                new = 0 if old == rc else old
            _set(R, a, old)
            mem[addr] = new
            sharers[ln] = {t}
            dirty[ln] = t
            wake(addr, _w32(now + cost))
        elif op == ADDI:
            _set(R, a, _w32(rb + imm))
        elif op == MOVI:
            _set(R, a, imm)
        elif op == MOV:
            _set(R, a, rb)
        elif op == SUB:
            _set(R, a, _w32(rb - rc))
        elif op == MULI:
            _set(R, a, _w32(rb * imm))
        elif op == ANDI:
            _set(R, a, rb & imm)
        elif op == HASH:
            _set(R, a, _w32(wa_base + ((_w32(rb * 127) ^ rc) & wa_mask)))
        elif op == HASHP:
            _set(R, a, _w32(wa_base + rc * wa_size + (_w32(rb * 127) & wa_mask)))
        elif BEQ <= op <= JMP:
            taken = {BEQ: ra == rb, BNE: ra != rb, BLE: ra <= rb, BGT: ra > rb,
                     BEQI: ra == c_, BNEI: ra != c_, BLEI: ra <= c_,
                     BGTI: ra > c_, JMP: True}[op]
            if taken:
                new_pc = imm
        elif op == WORKI:
            cost = max(imm, 1)
        elif op == WORKR:
            cost = max(ra, 1)
        elif op == PRNG:
            sd = (prng[t] * 1664525 + 1013904223) & 0xFFFFFFFF
            _set(R, a, (sd >> 16) % max(imm, 1))
            prng[t] = sd
        elif SPIN_EQ <= op <= SPIN_NEI or op == SPIN_GE:
            addr = _w32(rb + imm)
            ln = addr >> LINE_SHIFT
            cost = load_cost(t, ln)
            val = mem[addr]
            proceed = {SPIN_EQ: val == ra, SPIN_NE: val != ra,
                       SPIN_EQI: val == c_, SPIN_NEI: val != c_,
                       SPIN_GE: _w32(val - ra) >= 0}[op]
            sharers[ln].add(t)
            if not proceed:
                new_pc = pc[t]
                sleep = True
                spin_addr[t] = addr
        elif op == ACQ:
            waited = c_ > 0
            acq[t] += 1
            if waited:
                waited_acq[t] += 1
                if rel_time[ra] >= 0:
                    hand_sum = _w32(hand_sum + now - rel_time[ra])
                    hand_cnt += 1
                    rel_time[ra] = -1
            if acq_t0[t] >= 0:
                lat = max(_w32(now - acq_t0[t]), 0)
                lat_hist[sum(lat >= (1 << k)
                             for k in range(N_LAT_BUCKETS - 1))] += 1
                acq_t0[t] = -1
        elif op == TSTART:
            acq_t0[t] = now
        elif op == REL:
            rel_time[rb] = now
        elif op == HALT:
            cost = INF
            new_pc = pc[t]
        elif op != NOP:
            raise ValueError(f"unknown opcode {op}")

        pc[t] = new_pc
        next_time[t] = INF if sleep else _w32(now + cost)

    return {"acquisitions": acq, "waited_acquisitions": waited_acq,
            "handover_sum": hand_sum, "handover_count": hand_cnt,
            "events": events, "sleeping": sum(s >= 0 for s in spin_addr),
            "mem": mem, "lat_hist": lat_hist}


def hist_percentile(hist, q: float) -> float:
    """Percentile of a log2 latency histogram, at the bucket's upper edge."""
    total = sum(hist)
    if total == 0:
        return math.nan
    rank = max(1, math.ceil(q * total))
    run = 0
    for k, n in enumerate(hist):
        run += n
        if run >= rank:
            return float((1 << k) - 1 if k else 0)
    raise AssertionError("rank past the histogram")


def run_cell(*, lock: str, n_threads: int, seed: int, sweep: dict,
             n_locks: int = 1, private_arrays: bool = False,
             mutate: tuple = ()) -> dict:
    """The reference's numbers for one cell of a sweep over a pool of
    ``n_locks`` locks, with a shared waiting array or private ones.

    ``sweep`` holds the configuration's ``sweep`` keys (``cs_work``,
    ``ncs_max``, ``wa_size``, ``horizon``, ``max_events``,
    ``collect_latency``, ``costs`` and the preemption keys).
    """
    L = Layout(n_threads, sweep["wa_size"], n_locks=n_locks,
               private_arrays=private_arrays)
    # the control "shared_hash": the program of the shared arm in the
    # private arm's memory
    hashed = (Layout(n_threads, sweep["wa_size"], n_locks=n_locks)
              if "shared_hash" in mutate else L)
    prog = build_mutexbench(lock, hashed, cs_work=sweep["cs_work"],
                            ncs_max=sweep["ncs_max"],
                            collect_latency=sweep["collect_latency"])
    horizon, max_events = sweep["horizon"], sweep["max_events"]
    faults = fault_schedule(
        seed=seed, n_threads=n_threads,
        preempt_faults=sweep.get("preempt_faults", 0),
        preempt_cost=sweep.get("preempt_cost", 0), max_events=max_events,
        fault_evt_span=sweep.get("fault_evt_span"))
    raw = interpret(prog, n_threads=n_threads, n_locks=n_locks,
                    mem=init_mem(lock, L),
                    regs=init_regs(L), wa_base=L.wa_base, wa_size=L.wa_size,
                    horizon=horizon, max_events=max_events, seed=seed,
                    costs=[sweep["costs"][k] for k in COST_KEYS],
                    faults=faults, mutate=mutate)
    out = dict(raw)
    out["fault_schedule"] = [list(f) for f in faults]
    out["throughput"] = sum(raw["acquisitions"]) / horizon
    hc = raw["handover_count"]
    out["avg_handover"] = raw["handover_sum"] / hc if hc else math.nan
    if sweep["collect_latency"]:
        for name, q in (("lat_p50", 0.5), ("lat_p99", 0.99),
                        ("lat_p999", 0.999)):
            out[name] = hist_percentile(raw["lat_hist"], q)
    else:
        del out["lat_hist"]
    return out
