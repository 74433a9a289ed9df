// Native runtime core: the lock-free LIFO and the dependency table.
//
// Rebuild of two of the reference's foundation classes in C++ (SURVEY §2.1:
// parsec/class/{lifo,parsec_hash_table}), exposed through a C ABI for
// ctypes.  The LIFO holds opaque uint64 task handles for the ll/llp
// schedulers; the dependency table implements the satisfied-mask protocol
// of parsec_update_deps_with_mask (parsec.c:1577) with per-bucket locks
// (the hashed variant, parsec.c:1501).
//
// Design notes (not a translation):
// - LIFO push/pop use a 128-bit CAS {head, aba} pair to defeat ABA, the
//   same trick the reference's lifo.h uses, implemented with GCC __int128
//   atomics instead of hand-rolled asm.
// - The dep table is a fixed-power-of-two bucket array with chaining and a
//   spinlock per bucket; entries free-list onto a spinlocked list.
// - Handles are uint64 so Python can map them to task objects; the native
//   layer never owns Python state.

#include <atomic>
#include <cstddef>
#include <cstdint>
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
    Spin flock;                // guards free_head
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

}  // extern "C"
