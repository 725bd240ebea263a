"""Plain reference of DurableMSQ, the thinned durable queue of Friedman
et al. (PPoPP'18) that Sela & Petrank (SPAA'21, section 10) take as the
baseline, for one thread: two fences per enqueue (node content, then the
link), one per dequeue.  Transcribed from ``repro.core.durable_msq``
without recovery or the schedule tables; it imports nothing of the
system under test.
"""
from .memory import LINE_WORDS, NULL

ITEM, NEXT = 0, 1                 # node layout: one persistent line


class Queue:
    def __init__(self, mem, alloc):
        self.mem, self.alloc = mem, alloc
        base = mem.alloc_region(2 * LINE_WORDS)
        self.HEAD, self.TAIL = base, base + LINE_WORDS
        dummy = alloc.alloc()
        mem.write_full_line(dummy, [None, NULL, 0, 0, 0, 0, 0, 0])
        mem.write(self.HEAD, dummy)
        mem.write(self.TAIL, dummy)
        mem.pflush(dummy)
        mem.pflush(self.HEAD)
        mem.fence()

    def enqueue(self, item) -> None:
        mem = self.mem
        self.alloc.op_begin()
        node = self.alloc.alloc()
        mem.write_full_line(node, [item, NULL, 0, 0, 0, 0, 0, 0])
        mem.pflush(node)
        mem.fence()                         # node content durable
        while True:
            tail = mem.read(self.TAIL)
            nxt = mem.read(tail + NEXT)
            if nxt == NULL:
                if mem.cas(tail + NEXT, NULL, node):
                    self.persist_link(tail)
                    mem.cas(self.TAIL, tail, node)
                    return
            else:
                mem.pflush(tail + NEXT)
                mem.fence()
                mem.cas(self.TAIL, tail, nxt)

    def persist_link(self, tail) -> None:
        """The link to the new node is durable before the enqueue returns."""
        self.mem.pflush(tail + NEXT)
        self.mem.fence()

    def dequeue(self):
        mem = self.mem
        self.alloc.op_begin()
        while True:
            head = mem.read(self.HEAD)
            nxt = mem.read(head + NEXT)
            if nxt == NULL:
                mem.pflush(self.HEAD)
                mem.fence()
                return None
            tail = mem.read(self.TAIL)
            if head == tail:
                mem.pflush(tail + NEXT)
                mem.fence()
                mem.cas(self.TAIL, tail, nxt)
                continue
            item = mem.read(nxt + ITEM)
            if mem.cas(self.HEAD, head, nxt):
                mem.pflush(self.HEAD)
                mem.fence()
                self.alloc.retire(head)
                return item
