"""Plain reference of the simulated NVRAM and its allocators, one thread.

A straightforward per-primitive simulator of the memory semantics the
fleet executor reproduces (Sela & Petrank, SPAA'21, section 2): word
memory in 8-word lines, a cache in front of persistent memory, CLWB-like
flushes, SFENCE-like fences, non-temporal stores, and a volatile (DRAM)
space beside it.  It counts the same twelve events per primitive as the
system's cost engine and nothing else: no crash, no recovery, no
threads.  It imports nothing of the system under test; it is transcribed
from the repository's deliberately simple engine and allocator
(``repro.core.nvram_ref``, ``repro.core.ssmem``), cut to what one thread
running one plan needs.

The platform's behaviour comes from the configuration file
(``flush_invalidates``, ``needs_flush``, ``persist_on_store``), so a
configuration on another platform needs no change here.
"""
from __future__ import annotations

# the twelve event columns, in the order of the system's counts matrix
EVENTS = ("read", "write", "cas", "flush", "fence", "fence_line", "movnti",
          "hit", "dram", "cold_dram", "cold_nvm", "post_flush")
(READ, WRITE, CAS, FLUSH, FENCE, FENCE_LINE, MOVNTI, HIT, DRAM, COLD_DRAM,
 COLD_NVM, POST_FLUSH) = range(len(EVENTS))

LINE_WORDS = 8
NULL = 0
VOLATILE_BASE = 1 << 40

# line-state bits of persistent lines
CACHED, FLUSH_INVALID, EVER_FLUSHED = 1, 2, 4


class Memory:
    """One thread's view of a two-level memory, counting events."""

    def __init__(self, platform: dict):
        self.flush_invalidates = bool(platform["flush_invalidates"])
        self.needs_flush = bool(platform["needs_flush"])
        self.persist_on_store = bool(platform["persist_on_store"])
        self.counts = [0] * len(EVENTS)
        self.value = {}             # coherent view of every written word
        self.line_state = {}        # persistent line -> state bits
        self.vtouched = set()       # volatile words touched once
        self.pending = set()        # lines a fence has to drain
        self.brk = LINE_WORDS       # address 0 is NULL
        self.vbrk = VOLATILE_BASE

    def alloc_region(self, nwords: int, persistent: bool = True) -> int:
        if persistent:
            base = -(-self.brk // LINE_WORDS) * LINE_WORDS
            self.brk = base + nwords
        else:
            base = -(-self.vbrk // LINE_WORDS) * LINE_WORDS
            self.vbrk = base + nwords
        return base

    def _access(self, addr: int) -> None:
        """Account for one fetching access (read, write or CAS)."""
        c = self.counts
        if addr >= VOLATILE_BASE:
            c[HIT if addr in self.vtouched else DRAM] += 1
            self.vtouched.add(addr)
            return
        line = addr // LINE_WORDS
        s = self.line_state.get(line, 0)
        if s & CACHED:
            c[HIT] += 1
        elif s & FLUSH_INVALID:
            c[POST_FLUSH] += 1
        elif s & EVER_FLUSHED:
            c[COLD_NVM] += 1
        else:
            c[COLD_DRAM] += 1
        self.line_state[line] = (s & EVER_FLUSHED) | CACHED

    def read(self, addr: int):
        self.counts[READ] += 1
        self._access(addr)
        return self.value.get(addr)

    def write(self, addr: int, v) -> None:
        self.counts[WRITE] += 1
        self._access(addr)
        self.value[addr] = v

    def write_full_line(self, base: int, values) -> None:
        """A store of a whole line: no fetch, so never a post-flush access."""
        assert base % LINE_WORDS == 0 and len(values) <= LINE_WORDS
        self.counts[WRITE] += 1
        self.counts[HIT] += 1
        for k, v in enumerate(values):
            self.value[base + k] = v
        if base >= VOLATILE_BASE:
            self.vtouched.update(range(base, base + len(values)))
        else:
            line = base // LINE_WORDS
            self.line_state[line] = \
                (self.line_state.get(line, 0) & EVER_FLUSHED) | CACHED

    def cas(self, addr: int, expected, new) -> bool:
        self.counts[CAS] += 1
        self._access(addr)
        if self.value.get(addr) == expected:
            self.value[addr] = new
            return True
        return False

    def flush(self, addr: int) -> None:
        assert addr < VOLATILE_BASE, "flushing volatile memory"
        self.counts[FLUSH] += 1
        line = addr // LINE_WORDS
        self.pending.add(line)
        s = self.line_state.get(line, 0)
        self.line_state[line] = (FLUSH_INVALID | EVER_FLUSHED
                                 if self.flush_invalidates
                                 else s | EVER_FLUSHED)

    def movnti(self, addr: int, v) -> None:
        assert addr < VOLATILE_BASE
        self.counts[MOVNTI] += 1
        self.value[addr] = v
        self.pending.add(addr // LINE_WORDS)

    def fence(self) -> None:
        """Drains every distinct line with an outstanding flush or NT store."""
        self.counts[FENCE] += 1
        self.counts[FENCE_LINE] += len(self.pending)
        self.pending.clear()

    # the queues' model-aware persist helpers
    def pflush(self, addr: int) -> None:
        if self.needs_flush:
            self.flush(addr)


class SSMem:
    """Epoch-based designated-area allocator of persistent nodes (one line
    each), with the volatile node allocator that shares its epochs."""

    def __init__(self, mem: Memory, area_nodes: int):
        self.mem = mem
        self.area_nodes = area_nodes
        self.area = None
        self.cursor = 0
        self.free = []
        self.epoch = 0
        self.announced = 0
        self.limbo = []             # (addr, epoch, "p" | "v")
        self.ops_since_advance = 0
        self.valloc = None

    def _new_area(self) -> None:
        mem = self.mem
        base = mem.alloc_region(self.area_nodes * LINE_WORDS)
        for i in range(self.area_nodes):
            mem.write_full_line(base + i * LINE_WORDS, [0] * LINE_WORDS)
            mem.pflush(base + i * LINE_WORDS)
        mem.fence()
        self.area, self.cursor = base, 0

    def op_begin(self) -> None:
        self.announced = self.epoch
        self.ops_since_advance += 1
        if self.ops_since_advance >= 64:
            self.ops_since_advance = 0
            if self.announced >= self.epoch:
                self.epoch += 1
            cut = self.announced - 2
            while self.limbo and self.limbo[0][1] <= cut:
                addr, _, kind = self.limbo.pop(0)
                if kind == "p":
                    self.free.append(addr)
                else:
                    self.valloc.free.append(addr)

    def alloc(self) -> int:
        if self.free:
            return self.free.pop()
        if self.area is None or self.cursor >= self.area_nodes:
            self._new_area()
        addr = self.area + self.cursor * LINE_WORDS
        self.cursor += 1
        return addr

    def retire(self, addr: int) -> None:
        self.limbo.append((addr, self.epoch, "p"))

    def retire_volatile(self, addr: int) -> None:
        self.limbo.append((addr, self.epoch, "v"))


class VolatileAlloc:
    """Bump and free-list allocator of volatile nodes, in chunks."""

    def __init__(self, mem: Memory, node_words: int, chunk_nodes: int = 4096):
        self.mem = mem
        self.node_words = node_words
        self.chunk_nodes = chunk_nodes
        self.free = []
        self.base = None
        self.cursor = 0

    def alloc(self) -> int:
        if self.free:
            return self.free.pop()
        if self.base is None or self.cursor >= self.chunk_nodes:
            self.base = self.mem.alloc_region(
                self.chunk_nodes * self.node_words, persistent=False)
            self.cursor = 0
        addr = self.base + self.cursor * self.node_words
        self.cursor += 1
        return addr
