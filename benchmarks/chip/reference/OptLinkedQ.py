"""Plain reference of OptLinkedQ, the second amendment of LinkedQ (Sela &
Petrank, SPAA'21, sections 6.2-6.3), for one thread: a node is a
persistent half {item, index, pred} and a volatile half; an enqueue
flushes its own persistent half on a backward walk, writes the thread's
last-enqueue record with non-temporal stores and fences once; a dequeue
writes the head index with a non-temporal store and fences once.
Transcribed from ``repro.core.opt_linked`` without recovery or the
schedule tables; it imports nothing of the system under test.
"""
from .memory import LINE_WORDS, NULL, VolatileAlloc

P_ITEM, P_INDEX, P_PRED = 0, 1, 2
V_ITEM, V_INDEX, V_NEXT, V_PPTR, V_PREDV = 0, 1, 2, 3, 4
V_WORDS = 5
R_PEN_PTR, R_PEN_IDX, R_LAST_PTR, R_LAST_IDX = 0, 1, 2, 3


class Queue:
    def __init__(self, mem, alloc):
        self.mem, self.alloc = mem, alloc
        self.valloc = VolatileAlloc(mem, V_WORDS)
        alloc.valloc = self.valloc
        self.HEADIDX = mem.alloc_region(LINE_WORDS)
        self.LASTENQ = mem.alloc_region(2 * LINE_WORDS)  # thread + recovery
        self.HEAD = mem.alloc_region(1, persistent=False)
        self.TAIL = mem.alloc_region(1, persistent=False)
        self.persisted = set()
        self.last = (NULL, 0)
        mem.movnti(self.HEADIDX, 0)
        self.write_record(0, (NULL, 0), (NULL, 0))
        self.write_record(1, (NULL, 0), (NULL, 0))
        dummy_p = alloc.alloc()
        mem.write_full_line(dummy_p, [None, 0, NULL, 0, 0, 0, 0, 0])
        mem.pflush(dummy_p)
        mem.fence()
        self.persisted.add(dummy_p)
        dummy_v = self.new_vnode(None, 0, dummy_p, NULL)
        mem.write(self.HEAD, dummy_v)
        mem.write(self.TAIL, dummy_v)

    def write_record(self, slot, pen, last) -> None:
        """Penultimate before last, all non-temporal stores."""
        base = self.LASTENQ + slot * LINE_WORDS
        self.mem.movnti(base + R_PEN_PTR, pen[0])
        self.mem.movnti(base + R_PEN_IDX, pen[1])
        self.mem.movnti(base + R_LAST_PTR, last[0])
        self.mem.movnti(base + R_LAST_IDX, last[1])

    def new_vnode(self, item, idx, pptr, predv) -> int:
        mem = self.mem
        v = self.valloc.alloc()
        mem.write(v + V_ITEM, item)
        mem.write(v + V_INDEX, idx)
        mem.write(v + V_NEXT, NULL)
        mem.write(v + V_PPTR, pptr)
        mem.write(v + V_PREDV, predv)
        return v

    def enqueue(self, item) -> None:
        mem = self.mem
        self.alloc.op_begin()
        pnode = self.alloc.alloc()
        self.persisted.discard(pnode)
        mem.write_full_line(pnode, [item, 0, NULL, 0, 0, 0, 0, 0])
        vnode = self.new_vnode(item, 0, pnode, NULL)
        while True:
            tailv = mem.read(self.TAIL)
            if mem.read(tailv + V_NEXT) == NULL:
                idx = mem.read(tailv + V_INDEX) + 1
                predp = mem.read(tailv + V_PPTR)
                mem.write(pnode + P_PRED, predp)
                mem.write(pnode + P_INDEX, idx)          # index last
                mem.write(vnode + V_INDEX, idx)
                mem.write(vnode + V_PREDV, tailv)
                if mem.cas(tailv + V_NEXT, NULL, vnode):
                    walked = self.flush_walk(vnode)
                    self.write_record(0, self.last, (pnode, idx))
                    mem.fence()                         # the one fence
                    self.persisted.update(walked)
                    self.last = (pnode, idx)
                    mem.cas(self.TAIL, tailv, vnode)
                    return
            else:
                mem.cas(self.TAIL, tailv, mem.read(tailv + V_NEXT))

    def flush_walk(self, vnode) -> list:
        """Flush the persistent halves back to the first durable one."""
        mem, walked, pv = self.mem, [], vnode
        while pv != NULL:
            pp = mem.read(pv + V_PPTR)
            if pp in self.persisted:
                break
            mem.pflush(pp)
            walked.append(pp)
            pv = mem.read(pv + V_PREDV)
        return walked

    def dequeue(self):
        mem = self.mem
        self.alloc.op_begin()
        while True:
            headv = mem.read(self.HEAD)
            nxt = mem.read(headv + V_NEXT)
            if nxt == NULL:
                idx = mem.read(headv + V_INDEX)
                mem.movnti(self.HEADIDX, idx)
                mem.fence()
                return None
            tailv = mem.read(self.TAIL)
            if headv == tailv:
                mem.cas(self.TAIL, tailv, nxt)
                continue
            item = mem.read(nxt + V_ITEM)
            idx = mem.read(nxt + V_INDEX)
            if mem.cas(self.HEAD, headv, nxt):
                mem.movnti(self.HEADIDX, idx)
                mem.fence()                             # the one fence
                pp = mem.read(headv + V_PPTR)
                self.alloc.retire(pp)
                self.alloc.retire_volatile(headv)
                return item
