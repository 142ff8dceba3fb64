/* slotmgr.c — native slot manager for the device-windows IP table.
 *
 * Replaces the per-distinct-IP Python dict+LRU loop in
 * banjax_tpu/matcher/windows.py (slots_for_unique_ips) with one C call
 * per batch over the unique-IP span array.  PERF round 4 measured that
 * loop at ~15 ms/batch in the all-distinct-IP worst case — the dominant
 * residual on the host path once parse/encode went native.
 *
 * Exact-parity contract with the Python path (the dict loop stays as the
 * differential oracle, tests/unit/test_slotmgr.py):
 *
 *   - two passes per batch, like the Python loop's ordering: pass 1
 *     (sm_lookup_batch) resolves hits and stamps their recency with the
 *     batch sequence number; pass 2 (sm_place_misses) assigns misses in
 *     ip order, popping the free stack first and evicting only at
 *     capacity.
 *   - free-stack order: slots pop ascending (0, 1, 2, ...); grown slots
 *     drain after every pre-grow slot — identical to the Python list's
 *     pop() order across _grow_locked calls.
 *   - eviction victim: minimum (last_used, slot) over assigned, unpinned
 *     slots not touched by THIS batch (last_used < seq) — exactly
 *     np.argmin's first-minimum tie-break.  The order is KEPT between
 *     batches: the assigned slots are linked oldest stamp first as the
 *     two passes stamp them, one run a batch sequence number, and a run
 *     is put in slot order once, when the victims' walk reaches it.  The
 *     walk yields the same victim sequence as the per-miss argmin because
 *     nothing becomes MORE evictable mid-call (pins are frozen, recency
 *     only advances): a pinned slot is passed where it is met and stays
 *     in place, a placement goes to the tail.
 *   - refusal: when every candidate is pinned/touched, return -1 with
 *     earlier misses already placed — the Python loop's partial-state
 *     refusal, after which the caller splits the batch.
 *
 * Recency (last_used, int64 per slot) and pin counts (int32 per slot)
 * stay in caller-owned numpy arrays shared by pointer, so the Python
 * side's vectorized pin release and introspection keep working
 * unchanged; on this path ONLY the two passes write last_used (the kept
 * order mirrors it).  This table is the one owner of slot -> address:
 * the key bytes live in one slab, a stride a slot (a longer key keeps an
 * allocation of its own), and whoever wants an address of a slot reads
 * it here (sm_keys_of).
 *
 * Pure C ABI (no Python.h), loaded with ctypes — same convention as
 * fastparse.c.
 */

#include <stdint.h>
#include <stdlib.h>
#include <string.h>

#define SM_EVICT_KEY_STRIDE 104 /* shmstate.c WT_KEY_MAX */
#define SM_KEY_STRIDE SM_EVICT_KEY_STRIDE /* a slot's bytes of the slab */

typedef struct {
    int64_t k; /* which of the call's victims */
    uint8_t *p;
    int32_t len;
} sm_dead;

typedef struct {
    int64_t capacity;
    int64_t assigned;
    /* per-slot key bytes: slab[slot * SM_KEY_STRIDE ...] for a key that
     * fits the stride, else long_key[slot] (malloc'd; the array itself is
     * made when the first such key arrives); ip_len -1 = unassigned */
    uint8_t *slab;
    uint8_t **long_key;
    int32_t *ip_len;
    int64_t *tpos; /* slot -> its index in table (for O(1) delete) */
    /* open addressing, linear probe: value = slot, -1 empty, -2 tomb */
    int64_t *table;
    int64_t table_cap; /* power of two, >= 4 * capacity */
    int64_t tombs;
    /* free stack: pop from free_slots[free_top - 1] */
    int32_t *free_slots;
    int64_t free_top;
    /* the eviction order: every assigned slot, doubly linked, oldest
     * stamp first.  Runs (slots of one last_used) with last_used <=
     * sorted_lu are in slot order; a younger run is as it was stamped */
    int32_t *prev, *next;
    int32_t head, tail;
    int64_t sorted_lu;
    int32_t *run;    /* scratch [capacity]: one run while it is sorted */
    int64_t scanned; /* slots the victims' walks read, sorted runs' too */
    /* the last placement's victims whose key is longer than the stride
     * evict_keys cuts at: kept whole until the next placement */
    sm_dead *dead;
    int64_t n_dead, dead_cap;
    int64_t empty_victim; /* ... and which of them had the empty key, or -1 */
} sm_t;

static uint64_t sm_hash(const uint8_t *p, int64_t n) {
    uint64_t h = 1469598103934665603ULL;
    for (int64_t i = 0; i < n; i++) {
        h ^= p[i];
        h *= 1099511628211ULL;
    }
    return h;
}

static int64_t pow2_at_least(int64_t n) {
    int64_t c = 64;
    while (c < n)
        c <<= 1;
    return c;
}

static inline const uint8_t *sm_key(const sm_t *sm, int64_t slot) {
    return sm->ip_len[slot] > SM_KEY_STRIDE
               ? sm->long_key[slot]
               : sm->slab + slot * SM_KEY_STRIDE;
}

/* the slot holding the key, -1 when none does */
static inline int64_t sm_find(const sm_t *sm, const uint8_t *p, int64_t len) {
    uint64_t mask = (uint64_t)sm->table_cap - 1;
    uint64_t s = sm_hash(p, len) & mask;
    for (;;) {
        int64_t v = sm->table[s];
        if (v == -1)
            return -1;
        if (v >= 0 && sm->ip_len[v] == (int32_t)len &&
            memcmp(sm_key(sm, v), p, (size_t)len) == 0)
            return v;
        s = (s + 1) & mask;
    }
}

/* insertion index for a key known to be ABSENT: first tombstone on the
 * probe path, else the terminating empty cell */
static int64_t sm_insert_pos(const sm_t *sm, const uint8_t *p, int64_t len) {
    uint64_t mask = (uint64_t)sm->table_cap - 1;
    uint64_t s = sm_hash(p, len) & mask;
    int64_t first_tomb = -1;
    for (;;) {
        int64_t v = sm->table[s];
        if (v == -1)
            return first_tomb >= 0 ? first_tomb : (int64_t)s;
        if (v == -2 && first_tomb < 0)
            first_tomb = (int64_t)s;
        s = (s + 1) & mask;
    }
}

static void sm_table_insert(sm_t *sm, int32_t slot) {
    int64_t pos = sm_insert_pos(sm, sm_key(sm, slot), sm->ip_len[slot]);
    if (sm->table[pos] == -2)
        sm->tombs--;
    sm->table[pos] = slot;
    sm->tpos[slot] = pos;
}

static int sm_table_rebuild(sm_t *sm, int64_t min_cap) {
    int64_t want = pow2_at_least(4 * min_cap);
    if (want != sm->table_cap) {
        int64_t *t = realloc(sm->table, sizeof(int64_t) * (size_t)want);
        if (!t)
            return -1;
        sm->table = t;
        sm->table_cap = want;
    }
    for (int64_t i = 0; i < sm->table_cap; i++)
        sm->table[i] = -1;
    sm->tombs = 0;
    for (int64_t s = 0; s < sm->capacity; s++)
        if (sm->ip_len[s] >= 0)
            sm_table_insert(sm, (int32_t)s);
    return 0;
}

static void sm_drop_dead(sm_t *sm) {
    for (int64_t j = 0; j < sm->n_dead; j++)
        free(sm->dead[j].p);
    sm->n_dead = 0;
    sm->empty_victim = -1;
}

/* every slot unassigned, the free stack popping 0, 1, 2, ... —
 * list(range(cap-1, -1, -1)).pop() parity */
static void sm_reset(sm_t *sm) {
    for (int64_t s = 0; s < sm->capacity; s++) {
        if (sm->ip_len[s] > SM_KEY_STRIDE)
            free(sm->long_key[s]);
        sm->ip_len[s] = -1;
        sm->free_slots[s] = (int32_t)(sm->capacity - 1 - s);
    }
    sm->free_top = sm->capacity;
    sm->assigned = 0;
    sm->tombs = 0;
    for (int64_t i = 0; i < sm->table_cap; i++)
        sm->table[i] = -1;
    sm->head = sm->tail = -1;
    sm->sorted_lu = INT64_MIN;
    sm_drop_dead(sm);
}

void sm_destroy(void *h) {
    sm_t *sm = h;
    if (!sm)
        return;
    if (sm->ip_len && sm->free_slots && sm->table)
        sm_reset(sm);
    free(sm->slab);
    free(sm->long_key);
    free(sm->ip_len);
    free(sm->tpos);
    free(sm->free_slots);
    free(sm->table);
    free(sm->prev);
    free(sm->next);
    free(sm->run);
    free(sm->dead);
    free(sm);
}

void *sm_create(int64_t capacity) {
    if (capacity < 1)
        return NULL;
    sm_t *sm = calloc(1, sizeof(sm_t));
    if (!sm)
        return NULL;
    sm->capacity = capacity;
    sm->slab = malloc((size_t)capacity * SM_KEY_STRIDE);
    sm->ip_len = malloc(sizeof(int32_t) * (size_t)capacity);
    sm->tpos = calloc((size_t)capacity, sizeof(int64_t));
    sm->free_slots = malloc(sizeof(int32_t) * (size_t)capacity);
    sm->prev = malloc(sizeof(int32_t) * (size_t)capacity);
    sm->next = malloc(sizeof(int32_t) * (size_t)capacity);
    sm->run = malloc(sizeof(int32_t) * (size_t)capacity);
    sm->table_cap = pow2_at_least(4 * capacity);
    sm->table = malloc(sizeof(int64_t) * (size_t)sm->table_cap);
    if (!sm->slab || !sm->ip_len || !sm->tpos || !sm->free_slots ||
        !sm->prev || !sm->next || !sm->run || !sm->table) {
        sm->ip_len = NULL; /* nothing to reset */
        sm_destroy(sm);
        return NULL;
    }
    for (int64_t s = 0; s < capacity; s++)
        sm->ip_len[s] = -1;
    sm_reset(sm);
    return sm;
}

void sm_clear(void *h) { sm_reset(h); }

int64_t sm_assigned(void *h) { return ((sm_t *)h)->assigned; }

int64_t sm_free_count(void *h) { return ((sm_t *)h)->free_top; }

/* slots the victims' walks have read since the manager was made
 * (banjax_slot_eviction_scanned_slots_total) */
int64_t sm_scanned(void *h) { return ((sm_t *)h)->scanned; }

/* Extend to new_capacity.  New slots land at the BOTTOM of the free
 * stack (popped last, ascending) — matching the Python _grow_locked
 * free-list splice; the kept order is untouched.  Returns 0 ok, -1 on
 * allocation failure (manager left at the old capacity, still
 * consistent). */
int64_t sm_grow(void *h, int64_t new_capacity) {
    sm_t *sm = h;
    int64_t add = new_capacity - sm->capacity;
    if (add <= 0)
        return 0;
    size_t n = (size_t)new_capacity;
#define SM_GROW(field, bytes)                                                 \
    do {                                                                      \
        void *p_ = realloc(sm->field, (bytes));                               \
        if (!p_)                                                              \
            return -1;                                                        \
        sm->field = p_;                                                       \
    } while (0)
    SM_GROW(slab, n * SM_KEY_STRIDE);
    if (sm->long_key) {
        SM_GROW(long_key, sizeof(uint8_t *) * n);
        memset(sm->long_key + sm->capacity, 0, sizeof(uint8_t *) * (size_t)add);
    }
    SM_GROW(ip_len, sizeof(int32_t) * n);
    SM_GROW(tpos, sizeof(int64_t) * n);
    SM_GROW(free_slots, sizeof(int32_t) * n);
    SM_GROW(prev, sizeof(int32_t) * n);
    SM_GROW(next, sizeof(int32_t) * n);
    SM_GROW(run, sizeof(int32_t) * n);
#undef SM_GROW
    for (int64_t s = sm->capacity; s < new_capacity; s++)
        sm->ip_len[s] = -1;
    memmove(sm->free_slots + add, sm->free_slots,
            sizeof(int32_t) * (size_t)sm->free_top);
    for (int64_t i = 0; i < add; i++)
        sm->free_slots[i] = (int32_t)(new_capacity - 1 - i);
    sm->free_top += add;
    sm->capacity = new_capacity;
    if (sm->table_cap < 4 * new_capacity)
        /* rebuild OOM keeps the old table — denser but still valid
         * (assigned <= new_capacity <= table_cap / 2 after one double) */
        (void)sm_table_rebuild(sm, new_capacity);
    return 0;
}

/* ---- the kept eviction order ---- */

static inline void order_unlink(sm_t *sm, int32_t s) {
    int32_t p = sm->prev[s], n = sm->next[s];
    if (p >= 0)
        sm->next[p] = n;
    else
        sm->head = n;
    if (n >= 0)
        sm->prev[n] = p;
    else
        sm->tail = p;
}

/* link s behind `at` (-1: in front of everything) */
static inline void order_link_after(sm_t *sm, int32_t s, int32_t at) {
    int32_t n = at >= 0 ? sm->next[at] : sm->head;
    sm->prev[s] = at;
    sm->next[s] = n;
    if (at >= 0)
        sm->next[at] = s;
    else
        sm->head = s;
    if (n >= 0)
        sm->prev[n] = s;
    else
        sm->tail = s;
}

/* Stamp an unlinked slot with seq and give it its place.  The passes'
 * sequence numbers only advance, so that is the tail; a number that goes
 * back (no caller of the product's has one) is walked to its place in
 * the full (last_used, slot) order, which keeps a sorted run sorted. */
static inline void order_stamp(sm_t *sm, int32_t s, int64_t seq,
                               int64_t *last_used) {
    int32_t at = sm->tail;
    last_used[s] = seq;
    if (at >= 0 && (seq < last_used[at] || seq <= sm->sorted_lu))
        while (at >= 0 && (last_used[at] > seq ||
                           (last_used[at] == seq && at > s)))
            at = sm->prev[at];
    order_link_after(sm, s, at);
}

static int slot_cmp(const void *a, const void *b) {
    int32_t x = *(const int32_t *)a, y = *(const int32_t *)b;
    return (x > y) - (x < y);
}

/* Put the run that starts at `first` (the slots of its last_used) in
 * slot order, where it lies.  Returns the run's new first slot. */
static int32_t order_sort_run(sm_t *sm, int32_t first,
                              const int64_t *last_used) {
    int64_t lu = last_used[first], k = 0;
    int32_t before = sm->prev[first], s = first;
    while (s >= 0 && last_used[s] == lu) {
        sm->run[k++] = s;
        s = sm->next[s];
    }
    int32_t after = s;
    sm->scanned += k;
    sm->sorted_lu = lu;
    if (k > 1)
        qsort(sm->run, (size_t)k, sizeof(int32_t), slot_cmp);
    int32_t at = before;
    for (int64_t i = 0; i < k; i++) {
        s = sm->run[i];
        sm->prev[s] = at;
        if (at >= 0)
            sm->next[at] = s;
        else
            sm->head = s;
        at = s;
    }
    sm->next[at] = after;
    if (after >= 0)
        sm->prev[after] = at;
    else
        sm->tail = at;
    return sm->run[0];
}

/* The assigned slots in the kept order, oldest first; returns how many.
 * With last_used every run is sorted on the way, so out is the full
 * (last_used, slot) order of the table (tests compare it with the
 * selection below); without, the order is read as it lies. */
int64_t sm_order(void *h, const int64_t *last_used, int32_t *out) {
    sm_t *sm = h;
    int64_t n = 0;
    for (int32_t s = sm->head; s >= 0; s = sm->next[s]) {
        if (last_used && last_used[s] > sm->sorted_lu)
            s = order_sort_run(sm, s, last_used);
        out[n++] = s;
    }
    return n;
}

/* Pass 1: resolve every ip.  Hits get their slot in slots_out and their
 * recency stamped seq (the Python loop's vectorized hit touch); misses
 * get slots_out = -1 and their index appended to miss_idx_out.  Returns
 * the miss count. */
int64_t sm_lookup_batch(void *h, const uint8_t *blob, const int64_t *offs,
                        const int64_t *lens, int64_t n, int64_t seq,
                        int64_t *last_used, int32_t *slots_out,
                        int64_t *miss_idx_out) {
    sm_t *sm = h;
    int64_t n_miss = 0;
    for (int64_t i = 0; i < n; i++) {
        int64_t slot = sm_find(sm, blob + offs[i], lens[i]);
        slots_out[i] = (int32_t)slot;
        if (slot < 0) {
            miss_idx_out[n_miss++] = i;
        } else if (last_used[slot] != seq) {
            order_unlink(sm, (int32_t)slot);
            order_stamp(sm, (int32_t)slot, seq, last_used);
        }
    }
    return n_miss;
}

/* Read-only probe over a distinct-ip blob: the slot of every ip, -1
 * where it has none.  Like pass 1 but WITHOUT the recency stamp — the
 * admission gate must not refresh an IP's LRU position just for asking
 * whether it is resident (a refused batch would otherwise keep every
 * probe victim warm), and introspection (DeviceWindows.get reads a
 * resident address's record by its slot) must not either. */
void sm_find_batch(void *h, const uint8_t *blob, const int64_t *offs,
                   const int64_t *lens, int64_t n, int32_t *slots_out) {
    sm_t *sm = h;
    for (int64_t i = 0; i < n; i++)
        slots_out[i] = (int32_t)sm_find(sm, blob + offs[i], lens[i]);
}

/* The addresses of `slots`: lens_out[i] = the key's byte count, -1 for
 * an unassigned (or out of range) slot, and the keys one after the other
 * in buf while they fit cap.  Returns the bytes all of them take — a
 * caller whose buf was too small asks again with that much.
 * Introspection's route from a slot to its address; no window's. */
int64_t sm_keys_of(void *h, const int32_t *slots, int64_t n,
                   int32_t *lens_out, uint8_t *buf, int64_t cap) {
    sm_t *sm = h;
    int64_t total = 0;
    for (int64_t i = 0; i < n; i++) {
        int64_t s = slots[i];
        int32_t len = s >= 0 && s < sm->capacity ? sm->ip_len[s] : -1;
        lens_out[i] = len;
        if (len <= 0)
            continue;
        if (total + len <= cap)
            memcpy(buf + total, sm_key(sm, s), (size_t)len);
        total += len;
    }
    return total;
}

/* The last placement's victims whose key evict_keys cut: how many, and
 * the j-th one's place among the victims, length and bytes (valid until
 * the next placement, clear or destroy). */
int64_t sm_dead_count(void *h) { return ((sm_t *)h)->n_dead; }

/* ... and which victim's key was the empty address (evict_keys holds
 * the warm tier's one NUL byte for it), -1 when none's */
int64_t sm_empty_victim(void *h) { return ((sm_t *)h)->empty_victim; }

const uint8_t *sm_dead_key(void *h, int64_t j, int64_t *k_out,
                           int32_t *len_out) {
    sm_dead *d = &((sm_t *)h)->dead[j];
    *k_out = d->k;
    *len_out = d->len;
    return d->p;
}

/* Pass 2: place every miss, in ip order.  Free slots pop first; at
 * capacity the minimum-(last_used, slot) assigned, unpinned, untouched
 * slot is evicted (evict_out records them in order, and where
 * evict_keys is given, victim k's address bytes go to evict_keys[k *
 * SM_EVICT_KEY_STRIDE ...], cut at the stride — the warm tier's key
 * length — with their count in evict_key_lens[k]; the empty address is
 * one NUL byte, the tier's key for it: the spill of a victim's window
 * record needs the key and nothing else of the string; a key that was
 * cut stays whole behind sm_dead_key).  out_counts[0] = evictions
 * performed, out_counts[1] = misses successfully placed.
 * Returns 0, or -1 when an eviction was needed and every candidate is
 * pinned/touched (earlier misses stay placed and MUST be bookkept by
 * the caller — the Python refusal's partial-state semantics).
 *
 * The victims are the full (last_used, slot) order's, read off the kept
 * order from its head: a run is sorted by slot when the walk reaches it
 * (once — it only loses members after), a pinned slot is passed and
 * stays where it is, and the first slot this batch touched ends the
 * walk, since everything behind it was touched too: a refusal means no
 * evictable slot is left, as with a sort of the whole table.  The work
 * follows the misses, not the table: no allocation, no scan. */
int64_t sm_place_misses(void *h, const uint8_t *blob, const int64_t *offs,
                        const int64_t *lens, int64_t seq,
                        const int32_t *pin_counts, int64_t *last_used,
                        int32_t *slots_out, const int64_t *miss_idx,
                        int64_t n_miss, int64_t *evict_out,
                        int64_t *out_counts, uint8_t *evict_keys,
                        int32_t *evict_key_lens) {
    sm_t *sm = h;
    int64_t n_evict = 0, placed = 0, rc = 0;
    int32_t cur = sm->head; /* the walk's next slot */
    sm_drop_dead(sm);
    for (int64_t m = 0; m < n_miss; m++) {
        int64_t i = miss_idx[m];
        int64_t len = lens[i];
        uint8_t *own = NULL;
        if (len > SM_KEY_STRIDE) {
            if (!sm->long_key)
                sm->long_key = calloc((size_t)sm->capacity, sizeof(uint8_t *));
            own = sm->long_key ? malloc((size_t)len) : NULL;
            if (!own) {
                rc = -1; /* nothing taken: the caller retries smaller */
                break;
            }
        }
        int32_t slot = -1;
        if (sm->free_top > 0) {
            slot = sm->free_slots[--sm->free_top];
        } else {
            while (cur >= 0 && last_used[cur] < seq) {
                if (last_used[cur] > sm->sorted_lu)
                    cur = order_sort_run(sm, cur, last_used);
                int32_t s = cur;
                cur = sm->next[s];
                sm->scanned++;
                if (pin_counts[s] == 0) {
                    slot = s;
                    break;
                }
            }
            if (slot < 0) {
                free(own);
                rc = -1;
                break;
            }
            int32_t kl = sm->ip_len[slot];
            if (kl > SM_EVICT_KEY_STRIDE) {
                if (sm->n_dead == sm->dead_cap) {
                    int64_t cap = sm->dead_cap ? 2 * sm->dead_cap : 8;
                    sm_dead *d = realloc(sm->dead, sizeof(sm_dead) * (size_t)cap);
                    if (d) {
                        sm->dead = d;
                        sm->dead_cap = cap;
                    }
                }
                if (sm->n_dead < sm->dead_cap) {
                    sm_dead *d = &sm->dead[sm->n_dead++];
                    d->k = n_evict;
                    d->p = sm->long_key[slot];
                    d->len = kl;
                } else {
                    free(sm->long_key[slot]);
                }
                if (evict_keys)
                    memcpy(evict_keys + n_evict * SM_EVICT_KEY_STRIDE,
                           sm->long_key[slot], SM_EVICT_KEY_STRIDE);
                sm->long_key[slot] = NULL;
                kl = SM_EVICT_KEY_STRIDE;
            } else if (evict_keys) {
                uint8_t *dst = evict_keys + n_evict * SM_EVICT_KEY_STRIDE;
                memcpy(dst, sm_key(sm, slot), (size_t)kl);
                if (kl == 0) {
                    dst[kl++] = 0;
                    sm->empty_victim = n_evict;
                }
            }
            if (evict_keys)
                evict_key_lens[n_evict] = kl;
            order_unlink(sm, slot);
            sm->table[sm->tpos[slot]] = -2;
            sm->tombs++;
            sm->assigned--;
            evict_out[n_evict++] = slot;
        }
        if (own) {
            memcpy(own, blob + offs[i], (size_t)len);
            sm->long_key[slot] = own;
        } else {
            memcpy(sm->slab + (int64_t)slot * SM_KEY_STRIDE, blob + offs[i],
                   (size_t)len);
        }
        sm->ip_len[slot] = (int32_t)len;
        if ((sm->assigned + sm->tombs) * 2 > sm->table_cap)
            sm_table_rebuild(sm, sm->capacity);
        sm_table_insert(sm, slot);
        sm->assigned++;
        order_stamp(sm, slot, seq, last_used);
        slots_out[i] = slot;
        placed++;
    }
    out_counts[0] = n_evict;
    out_counts[1] = placed;
    return rc;
}

/* ---- the selection the kept order is held to (tests only) ---- */

typedef struct {
    int64_t lu;
    int32_t slot;
} sm_cand;

static inline int cand_lt(const sm_cand *x, const sm_cand *y) {
    return x->lu != y->lu ? x->lu < y->lu : x->slot < y->slot;
}

static int cand_cmp(const void *a, const void *b) {
    const sm_cand *x = a, *y = b;
    return cand_lt(x, y) ? -1 : (cand_lt(y, x) ? 1 : 0);
}

/* Reorder c[0..n) so that c[0..k) are its k smallest (lu, slot), in no
 * particular order (quickselect, median of three; the keys are distinct
 * because the slots are). */
static void cand_select(sm_cand *c, int64_t n, int64_t k) {
    int64_t lo = 0, hi = n - 1;
    while (lo < hi) {
        int64_t mid = lo + (hi - lo) / 2;
        sm_cand a = c[lo], b = c[mid], d = c[hi];
        sm_cand pivot = cand_lt(&a, &b)
                            ? (cand_lt(&b, &d) ? b : (cand_lt(&a, &d) ? d : a))
                            : (cand_lt(&a, &d) ? a : (cand_lt(&b, &d) ? d : b));
        int64_t i = lo, j = hi;
        while (i <= j) {
            while (cand_lt(&c[i], &pivot))
                i++;
            while (cand_lt(&pivot, &c[j]))
                j--;
            if (i <= j) {
                sm_cand t = c[i];
                c[i] = c[j];
                c[j] = t;
                i++;
                j--;
            }
        }
        /* c[lo..j] <= pivot <= c[i..hi]; whatever lies between is the pivot */
        if (k - 1 <= j)
            hi = j;
        else if (k - 1 >= i)
            lo = i;
        else
            return;
    }
}

/* Put the next `want` candidates of the full (lu, slot) order behind the
 * `sorted` already in order.  c[sorted..n) holds everything not yet
 * ordered, all of it >= c[sorted - 1].  Returns the new sorted count. */
static int64_t cand_extend(sm_cand *c, int64_t n, int64_t sorted,
                           int64_t want) {
    int64_t rest = n - sorted;
    if (want > rest)
        want = rest;
    if (want <= 0)
        return sorted;
    if (want < rest)
        cand_select(c + sorted, rest, want);
    qsort(c + sorted, (size_t)want, sizeof(sm_cand), cand_cmp);
    return sorted + want;
}

/* Test hook: the full (last_used, slot) order of n candidates, built
 * `chunk` at a time by the selection above — what placement does when it
 * has to go on past its first selection.  Writes the slots in order. */
void sm_test_select_order(const int64_t *lu, const int32_t *slot, int64_t n,
                          int64_t chunk, int32_t *out) {
    sm_cand *c = malloc(sizeof(sm_cand) * (size_t)(n > 0 ? n : 1));
    if (!c)
        return;
    for (int64_t i = 0; i < n; i++) {
        c[i].lu = lu[i];
        c[i].slot = slot[i];
    }
    int64_t sorted = 0;
    while (sorted < n)
        sorted = cand_extend(c, n, sorted, chunk > 0 ? chunk : 1);
    for (int64_t i = 0; i < n; i++)
        out[i] = c[i].slot;
    free(c);
}

static uint32_t crc_table[256];
static int crc_ready; /* benign race: every thread writes the same values */

static void crc_init(void) {
    if (__atomic_load_n(&crc_ready, __ATOMIC_ACQUIRE))
        return;
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = i;
        for (int k = 0; k < 8; k++)
            c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
        crc_table[i] = c;
    }
    __atomic_store_n(&crc_ready, 1, __ATOMIC_RELEASE);
}

static inline uint32_t crc32_of(const uint8_t *p, int64_t len) {
    uint32_t c = 0xFFFFFFFFu;
    for (int64_t k = 0; k < len; k++)
        c = crc_table[(c ^ p[k]) & 0xFF] ^ (c >> 8);
    return c ^ 0xFFFFFFFFu;
}

/* zlib's CRC-32 of each span (the traffic sketch's base hash of a client
 * address, obs/sketch.py hash_ip): the spans are the one encoding of a
 * batch's distinct addresses, so the sketch hashes what the slot table
 * and the warm tier are asked about, with no second walk in Python. */
void sm_crc32_batch(const uint8_t *blob, const int64_t *offs,
                    const int64_t *lens, int64_t n, uint32_t *out) {
    crc_init();
    for (int64_t i = 0; i < n; i++)
        out[i] = crc32_of(blob + offs[i], lens[i]);
}

/* One batch's distinct addresses from its shards' tables, by bytes.
 * Part p has a table of table_n[p] distinct keys — spans (offs[p],
 * lens[p]) of bufs[p] — and rows_n[p] rows, row r holding entry
 * row_inv[p][r].  The merged table takes the keys in first-appearance
 * order as the string merge has it (workset.CompositeWork.unique_ips):
 * part after part, a part's entries in its table's order, those no row
 * of the part holds left out.  Out: the merged keys back to back in
 * out_buf (one NUL behind the last: the warm tier's key for the empty
 * address), their spans and zlib CRC-32s (which the sketch folds under
 * and the merge probes by), and out_inv, every row's merged index, part
 * after part.  Scratch: table, table_cap a power of two >= twice the
 * entries of all parts; map, as many int64 as the largest part's.
 * Returns the merged count. */
int64_t sm_merge_spans(int64_t n_parts, const uint8_t *const *bufs,
                       const int64_t *const *offs, const int64_t *const *lens,
                       const int64_t *table_n, const int64_t *const *row_inv,
                       const int64_t *rows_n, int64_t *table,
                       int64_t table_cap, int64_t *map, uint8_t *out_buf,
                       int64_t *out_offs, int64_t *out_lens,
                       uint32_t *out_hash, int64_t *out_inv) {
    crc_init();
    for (int64_t i = 0; i < table_cap; i++)
        table[i] = -1;
    uint64_t mask = (uint64_t)table_cap - 1;
    int64_t n = 0, bytes = 0;
    for (int64_t p = 0; p < n_parts; p++) {
        const uint8_t *buf = bufs[p];
        const int64_t *inv = row_inv[p];
        for (int64_t j = 0; j < table_n[p]; j++)
            map[j] = -1;
        for (int64_t r = 0; r < rows_n[p]; r++)
            map[inv[r]] = -2; /* some row holds it */
        for (int64_t j = 0; j < table_n[p]; j++) {
            if (map[j] != -2)
                continue;
            const uint8_t *key = buf + offs[p][j];
            int64_t len = lens[p][j];
            uint32_t hash = crc32_of(key, len);
            uint64_t s = hash & mask;
            int64_t id;
            while ((id = table[s]) >= 0 &&
                   !(out_hash[id] == hash && out_lens[id] == len &&
                     memcmp(out_buf + out_offs[id], key, (size_t)len) == 0))
                s = (s + 1) & mask;
            if (id < 0) {
                id = table[s] = n++;
                memcpy(out_buf + bytes, key, (size_t)len);
                out_offs[id] = bytes;
                out_lens[id] = len;
                out_hash[id] = hash;
                bytes += len;
            }
            map[j] = id;
        }
        for (int64_t r = 0; r < rows_n[p]; r++)
            out_inv[r] = map[inv[r]];
        out_inv += rows_n[p];
    }
    out_buf[bytes] = 0;
    return n;
}
