// Native runtime core: concurrent queues, dependency table, counters.
//
// Rebuild of the reference's foundation-class tier in C++ (SURVEY §2.1:
// parsec/class/{lifo,dequeue,parsec_hash_table,maxheap} and the atomic
// counter discipline of parsec_internal.h:124-144), exposed through a C ABI
// for ctypes.  These are the dispatch hot-path structures: scheduler queues
// hold opaque uint64 task handles; the dependency table implements the
// satisfied-mask protocol of parsec_update_deps_with_mask (parsec.c:1577)
// with per-bucket locks (the hashed variant, parsec.c:1501).
//
// Design notes (not a translation):
// - LIFO push/pop use a 128-bit CAS {head, aba} pair to defeat ABA, the
//   same trick the reference's lifo.h uses, implemented with GCC __int128
//   atomics instead of hand-rolled asm.
// - The dep table is a fixed-power-of-two bucket array with chaining and a
//   spinlock per bucket; entries free-list onto a per-table LIFO.
// - Handles are uint64 so Python can map them to task objects; the native
//   layer never owns Python state.

#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <mutex>
#include <new>
#include <queue>
#include <utility>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// spinlock
// ---------------------------------------------------------------------------
struct Spin {
    std::atomic_flag f = ATOMIC_FLAG_INIT;
    void lock() {
        while (f.test_and_set(std::memory_order_acquire)) {
#if defined(__x86_64__)
            __builtin_ia32_pause();
#endif
        }
    }
    void unlock() { f.clear(std::memory_order_release); }
};

// ---------------------------------------------------------------------------
// lock-free LIFO with ABA counter (cf. class/lifo.h's 128-bit CAS design)
// ---------------------------------------------------------------------------
struct LifoNode {
    LifoNode* next;
    uint64_t value;
};

struct alignas(16) LifoHead {
    LifoNode* ptr;
    uint64_t aba;
};

struct Lifo {
    std::atomic<__int128> head;   // {ptr, aba} packed
    std::atomic<long> size;
    // node freelist to avoid malloc per push
    std::atomic<__int128> freelist;

    static __int128 pack(LifoNode* p, uint64_t aba) {
        __int128 v = (unsigned __int128)(uintptr_t)p;
        v |= ((unsigned __int128)aba) << 64;
        return v;
    }
    static LifoNode* ptr_of(__int128 v) {
        return (LifoNode*)(uintptr_t)(uint64_t)(unsigned __int128)v;
    }
    static uint64_t aba_of(__int128 v) {
        return (uint64_t)(((unsigned __int128)v) >> 64);
    }
};

static void lifo_stack_push(std::atomic<__int128>* stack, LifoNode* n) {
    __int128 old = stack->load(std::memory_order_relaxed);
    for (;;) {
        n->next = Lifo::ptr_of(old);
        __int128 desired = Lifo::pack(n, Lifo::aba_of(old) + 1);
        if (stack->compare_exchange_weak(old, desired,
                                         std::memory_order_release,
                                         std::memory_order_relaxed))
            return;
    }
}

static LifoNode* lifo_stack_pop(std::atomic<__int128>* stack) {
    __int128 old = stack->load(std::memory_order_acquire);
    for (;;) {
        LifoNode* n = Lifo::ptr_of(old);
        if (!n) return nullptr;
        __int128 desired = Lifo::pack(n->next, Lifo::aba_of(old) + 1);
        if (stack->compare_exchange_weak(old, desired,
                                         std::memory_order_acquire,
                                         std::memory_order_relaxed))
            return n;
    }
}

void* pt_lifo_new() {
    Lifo* l = new Lifo();
    l->head.store(0);
    l->freelist.store(0);
    l->size.store(0);
    return l;
}

void pt_lifo_free(void* h) {
    Lifo* l = (Lifo*)h;
    LifoNode* n;
    while ((n = lifo_stack_pop(&l->head))) delete n;
    while ((n = lifo_stack_pop(&l->freelist))) delete n;
    delete l;
}

void pt_lifo_push(void* h, uint64_t value) {
    Lifo* l = (Lifo*)h;
    LifoNode* n = lifo_stack_pop(&l->freelist);
    if (!n) n = new LifoNode();
    n->value = value;
    lifo_stack_push(&l->head, n);
    l->size.fetch_add(1, std::memory_order_relaxed);
}

// returns 1 and sets *out on success, 0 when empty
int pt_lifo_pop(void* h, uint64_t* out) {
    Lifo* l = (Lifo*)h;
    LifoNode* n = lifo_stack_pop(&l->head);
    if (!n) return 0;
    *out = n->value;
    l->size.fetch_sub(1, std::memory_order_relaxed);
    lifo_stack_push(&l->freelist, n);
    return 1;
}

long pt_lifo_size(void* h) {
    return ((Lifo*)h)->size.load(std::memory_order_relaxed);
}

// ---------------------------------------------------------------------------
// dequeue (cf. class/dequeue.h): two-ended, spinlocked
// ---------------------------------------------------------------------------
struct Deque {
    Spin lock;
    std::deque<uint64_t> q;
};

void* pt_deque_new() { return new Deque(); }
void pt_deque_free(void* h) { delete (Deque*)h; }

void pt_deque_push_back(void* h, uint64_t v) {
    Deque* d = (Deque*)h;
    d->lock.lock();
    d->q.push_back(v);
    d->lock.unlock();
}

void pt_deque_push_front(void* h, uint64_t v) {
    Deque* d = (Deque*)h;
    d->lock.lock();
    d->q.push_front(v);
    d->lock.unlock();
}

int pt_deque_pop_front(void* h, uint64_t* out) {
    Deque* d = (Deque*)h;
    d->lock.lock();
    if (d->q.empty()) { d->lock.unlock(); return 0; }
    *out = d->q.front();
    d->q.pop_front();
    d->lock.unlock();
    return 1;
}

int pt_deque_pop_back(void* h, uint64_t* out) {
    Deque* d = (Deque*)h;
    d->lock.lock();
    if (d->q.empty()) { d->lock.unlock(); return 0; }
    *out = d->q.back();
    d->q.pop_back();
    d->lock.unlock();
    return 1;
}

long pt_deque_size(void* h) {
    Deque* d = (Deque*)h;
    d->lock.lock();
    long n = (long)d->q.size();
    d->lock.unlock();
    return n;
}

// ---------------------------------------------------------------------------
// priority heap (cf. class/maxheap.c): (priority, handle) max-heap
// ---------------------------------------------------------------------------
struct Heap {
    Spin lock;
    std::priority_queue<std::pair<int64_t, uint64_t>> q;
};

void* pt_heap_new() { return new Heap(); }
void pt_heap_free(void* h) { delete (Heap*)h; }

void pt_heap_push(void* h, int64_t priority, uint64_t v) {
    Heap* p = (Heap*)h;
    p->lock.lock();
    p->q.emplace(priority, v);
    p->lock.unlock();
}

int pt_heap_pop(void* h, uint64_t* out) {
    Heap* p = (Heap*)h;
    p->lock.lock();
    if (p->q.empty()) { p->lock.unlock(); return 0; }
    *out = p->q.top().second;
    p->q.pop();
    p->lock.unlock();
    return 1;
}

long pt_heap_size(void* h) {
    Heap* p = (Heap*)h;
    p->lock.lock();
    long n = (long)p->q.size();
    p->lock.unlock();
    return n;
}

// ---------------------------------------------------------------------------
// dependency table: key -> {required_mask, satisfied_mask}
// (parsec_update_deps_with_mask, parsec.c:1577; hashed storage :1501)
// ---------------------------------------------------------------------------
struct DepEntry {
    uint64_t key;
    uint64_t required;
    uint64_t satisfied;
    DepEntry* next;
};

struct DepTable {
    size_t nbuckets;           // power of two
    std::vector<DepEntry*> buckets;
    std::vector<Spin> locks;
    std::atomic<long> count;
    std::atomic<__int128> freelist;   // of DepEntry via LifoNode-compatible
                                      // layout (next is first member? no —
                                      // use own simple spinlocked freelist)
    Spin flock;
    DepEntry* free_head = nullptr;

    explicit DepTable(size_t n) : nbuckets(n), buckets(n, nullptr),
                                  locks(n), count(0) {}
};

void* pt_deptable_new(uint64_t nbuckets_pow2) {
    size_t n = 1;
    while (n < nbuckets_pow2) n <<= 1;
    return new DepTable(n);
}

void pt_deptable_free(void* h) {
    DepTable* t = (DepTable*)h;
    for (size_t i = 0; i < t->nbuckets; i++) {
        DepEntry* e = t->buckets[i];
        while (e) { DepEntry* nx = e->next; delete e; e = nx; }
    }
    DepEntry* e = t->free_head;
    while (e) { DepEntry* nx = e->next; delete e; e = nx; }
    delete t;
}

static inline size_t dep_bucket(DepTable* t, uint64_t key) {
    // fibonacci hashing spreads sequential task keys
    return (size_t)((key * 0x9E3779B97F4A7C15ull) >> 32) & (t->nbuckets - 1);
}

// Record satisfied bits for `key`; required_mask is idempotently installed
// on first touch.  Returns 1 when the task just became ready (entry is
// removed), 0 otherwise.  A caller that has not evaluated the mask yet
// passes required_mask == 0 (no task waits for nothing): an existing entry
// is updated as usual, a missing one is NOT created and the call returns 2,
// so only the arrival that creates the entry pays for the mask.  Asserting
// a bit twice aborts (the double-release paranoia check,
// PARSEC_DEBUG_PARANOID analog) — returns -1 instead; a bit outside the
// entry's required mask (a release that named the wrong input dep, which
// would leave the task unready for ever) returns -2.
int pt_deptable_release(void* h, uint64_t key, uint64_t bits,
                        uint64_t required_mask) {
    DepTable* t = (DepTable*)h;
    size_t b = dep_bucket(t, key);
    t->locks[b].lock();
    DepEntry** slot = &t->buckets[b];
    DepEntry* e = *slot;
    while (e && e->key != key) { slot = &e->next; e = e->next; }
    if (!e) {
        if (!required_mask) {
            t->locks[b].unlock();
            return 2;                    // entry missing: come back with the mask
        }
        t->flock.lock();
        e = t->free_head;
        if (e) t->free_head = e->next;
        t->flock.unlock();
        if (!e) e = new DepEntry();
        e->key = key;
        e->required = required_mask;
        e->satisfied = 0;
        e->next = t->buckets[b];
        t->buckets[b] = e;
        slot = &t->buckets[b];
        t->count.fetch_add(1, std::memory_order_relaxed);
    }
    if ((e->satisfied & bits) || (bits & ~e->required)) {
        int rc = (e->satisfied & bits) ? -1 : -2;
        t->locks[b].unlock();
        return rc;                       // double release / not awaited
    }
    e->satisfied |= bits;
    int ready = (e->satisfied == e->required);
    if (ready) {
        *slot = e->next;
        t->count.fetch_sub(1, std::memory_order_relaxed);
        t->flock.lock();
        e->next = t->free_head;
        t->free_head = e;
        t->flock.unlock();
    }
    t->locks[b].unlock();
    return ready;
}

long pt_deptable_count(void* h) {
    return ((DepTable*)h)->count.load(std::memory_order_relaxed);
}

// ---------------------------------------------------------------------------
// DAG executor: the select→release inner loop of a *compiled* task graph
// (the jdf2c stance applied to the scheduler: scheduling.c:562-575's hot loop
// over a concretely-enumerated DAG).  Python hands over indegree counts and a
// CSR successor table once, then ping-pongs batches: pt_dag_fetch fills a
// buffer of ready task ids (priority order when priorities exist), Python
// runs the chore bodies, pt_dag_complete releases all successors of the
// batch natively and banks the newly-ready set.  Per-task native cost is a
// few array ops; Python appears only at the chore boundary.
// ---------------------------------------------------------------------------
struct Dag {
    Spin lock;
    int32_t ntasks;
    int64_t remaining;           // tasks not yet completed
    std::vector<int32_t> indeg;  // live remaining-input counters
    std::vector<int32_t> succ_off;
    std::vector<int32_t> succ;
    std::vector<int64_t> prio;
    bool use_prio;
    std::vector<int32_t> ready;                          // LIFO when !use_prio
    std::priority_queue<std::pair<int64_t, int32_t>> pready;
};

void* pt_dag_new(int32_t ntasks, const int32_t* indeg,
                 const int32_t* succ_off, const int32_t* succ,
                 const int64_t* prio) {
    Dag* d = new Dag();
    d->ntasks = ntasks;
    d->remaining = ntasks;
    d->indeg.assign(indeg, indeg + ntasks);
    d->succ_off.assign(succ_off, succ_off + ntasks + 1);
    d->succ.assign(succ, succ + succ_off[ntasks]);
    d->use_prio = (prio != nullptr);
    if (prio) d->prio.assign(prio, prio + ntasks);
    for (int32_t i = 0; i < ntasks; i++) {
        if (d->indeg[i] == 0) {
            if (d->use_prio) d->pready.emplace(d->prio[i], i);
            else d->ready.push_back(i);
        }
    }
    return d;
}

void pt_dag_free(void* h) { delete (Dag*)h; }

// Fill out[0..cap) with ready task ids; returns the count (0 = none ready).
int32_t pt_dag_fetch(void* h, int32_t* out, int32_t cap) {
    Dag* d = (Dag*)h;
    d->lock.lock();
    int32_t n = 0;
    if (d->use_prio) {
        while (n < cap && !d->pready.empty()) {
            out[n++] = d->pready.top().second;
            d->pready.pop();
        }
    } else {
        while (n < cap && !d->ready.empty()) {
            out[n++] = d->ready.back();
            d->ready.pop_back();
        }
    }
    d->lock.unlock();
    return n;
}

// Complete a batch: release every successor edge, banking newly-ready tasks.
// Returns the number of tasks still outstanding (0 = DAG fully executed),
// or -1 if a successor counter underflowed (graph inconsistency).
int64_t pt_dag_complete(void* h, const int32_t* done, int32_t n) {
    Dag* d = (Dag*)h;
    d->lock.lock();
    for (int32_t j = 0; j < n; j++) {
        int32_t t = done[j];
        for (int32_t e = d->succ_off[t]; e < d->succ_off[t + 1]; e++) {
            int32_t s = d->succ[e];
            if (--d->indeg[s] == 0) {
                if (d->use_prio) d->pready.emplace(d->prio[s], s);
                else d->ready.push_back(s);
            } else if (d->indeg[s] < 0) {
                d->lock.unlock();
                return -1;
            }
        }
    }
    d->remaining -= n;
    int64_t rem = d->remaining;
    d->lock.unlock();
    return rem;
}

int64_t pt_dag_remaining(void* h) {
    Dag* d = (Dag*)h;
    d->lock.lock();
    int64_t r = d->remaining;
    d->lock.unlock();
    return r;
}

// ---------------------------------------------------------------------------
// atomic counter with zero detection (the nb_tasks/nb_pending_actions
// discipline: the transition TO zero must be observed exactly once)
// ---------------------------------------------------------------------------
struct Counter {
    std::atomic<int64_t> v;
};

void* pt_counter_new(int64_t init) {
    Counter* c = new Counter();
    c->v.store(init);
    return c;
}
void pt_counter_free(void* h) { delete (Counter*)h; }

// returns the new value; caller fires termination iff it observes 0
int64_t pt_counter_add(void* h, int64_t delta) {
    return ((Counter*)h)->v.fetch_add(delta, std::memory_order_acq_rel)
           + delta;
}

int64_t pt_counter_get(void* h) {
    return ((Counter*)h)->v.load(std::memory_order_acquire);
}

}  // extern "C"
