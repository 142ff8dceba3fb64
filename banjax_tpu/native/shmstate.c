/* Shared-memory fixed-window rate-limit table.
 *
 * Backs the failed-challenge rate limiter when the HTTP request API runs
 * as multiple SO_REUSEPORT worker processes: every worker maps the same
 * shared-memory segment, so an IP failing challenges round-robined across
 * workers is counted exactly once, like the reference's single-process
 * mutex-guarded map (/root/reference/internal/rate_limit.go:105-156).
 *
 * Layout: one 128-byte header then capacity (power of two) 128-byte slots.
 * Open addressing with linear probing, bounded at FC_MAX_PROBE; no
 * deletion (lookup never early-stops on stolen slots, so probe chains
 * stay valid).  When a key's probe window is full, the stalest expired
 * slot in the window is stolen — semantically identical to keeping it,
 * because an expired window restarts as if first-seen (OUTSIDE_INTERVAL
 * resets hits to 1 exactly like FIRST_TIME does).  If nothing in the
 * window is expired the apply degrades to an unstored first hit and a
 * dropped counter is bumped (visible in metrics).
 *
 * Concurrency: one per-slot spinlock (acquire/release atomics); at most
 * one lock is ever held at a time.  Critical sections are a handful of
 * loads/stores.
 *
 * The lock word stores the OWNER'S PID (0 = free), not a plain flag, so
 * a worker SIGKILLed mid-critical-section (OOM-kill, supervisor
 * escalation) cannot wedge every survivor whose probe chain crosses the
 * slot: a waiter that observes a dead owner (kill(pid, 0) == ESRCH)
 * steals the lock immediately, and any owner — dead or merely wedged —
 * is stolen from after a bounded wall-clock spin (default 50 ms; the
 * critical sections are a few ns, so a live owner held that long is
 * itself a failure).  Unlock is a CAS from our own pid so a robbed
 * owner's late unlock cannot release the thief's lock.  The worst case
 * of a false steal is one corrupted rate-limit slot, never a hang.
 */

#include <errno.h>
#include <signal.h>
#include <stdint.h>
#include <string.h>
#include <time.h>
#include <unistd.h>

#define FC_MAGIC 0x626a7868736d3032LL /* "bjxhsm02" — owner-pid lock words */
#define FC_MAX_PROBE 64
#define FC_KEY_MAX 104

/* match_type values mirror banjax_tpu.decisions.rate_limit.RateLimitMatchType */
#define FC_FIRST_TIME 0
#define FC_OUTSIDE_INTERVAL 1
#define FC_INSIDE_INTERVAL 2
#define FC_EXCEEDED_BIT 0x10
#define FC_DROPPED_BIT 0x100

typedef struct {
    int64_t magic;
    int64_t capacity; /* slots; power of two */
    volatile int64_t dropped;
    int64_t _pad[13];
} fc_header; /* 128 bytes */

typedef struct {
    volatile int32_t lock;
    int32_t key_len; /* 0 = empty */
    int64_t interval_start_ns;
    int32_t num_hits;
    int32_t _pad;
    char key[FC_KEY_MAX];
} fc_slot; /* 128 bytes */

static int64_t fc_steal_after_ns = 50 * 1000 * 1000; /* 50 ms default */

/* test hook: lower the steal bound so the live-owner-steal path is
 * provable without a 50 ms wait per case */
void fc_set_steal_ns(int64_t ns) { fc_steal_after_ns = ns; }

static inline int32_t fc_self_tag(void) {
    /* benign race: every thread of a process writes the same value */
    static int32_t tag;
    if (tag == 0) {
        tag = (int32_t)getpid();
        if (tag == 0)
            tag = 1;
    }
    return tag;
}

static inline int64_t fc_mono_ns(void) {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (int64_t)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

static void fc_lock(fc_slot *s) {
    int32_t tag = fc_self_tag();
    int32_t expected = 0;
    if (__atomic_compare_exchange_n(&s->lock, &expected, tag, 0,
                                    __ATOMIC_ACQUIRE, __ATOMIC_RELAXED))
        return; /* uncontended fast path */
    int64_t t0 = 0;
    int32_t spins = 0;
    for (;;) {
        int32_t owner = __atomic_load_n(&s->lock, __ATOMIC_RELAXED);
        if (owner == 0) {
            expected = 0;
            if (__atomic_compare_exchange_n(&s->lock, &expected, tag, 0,
                                            __ATOMIC_ACQUIRE,
                                            __ATOMIC_RELAXED))
                return;
            continue;
        }
        if (++spins >= 1024) { /* syscalls only every ~1k spins */
            spins = 0;
            int64_t now = fc_mono_ns();
            if (t0 == 0)
                t0 = now;
            int dead = (owner != tag && kill((pid_t)owner, 0) != 0 &&
                        errno == ESRCH);
            if (dead || now - t0 > fc_steal_after_ns) {
                if (__atomic_compare_exchange_n(&s->lock, &owner, tag, 0,
                                                __ATOMIC_ACQUIRE,
                                                __ATOMIC_RELAXED))
                    return; /* stolen from a dead/wedged owner */
            }
        }
    }
}

static inline void fc_unlock(fc_slot *s) {
    /* release only if still ours: if the lock was stolen (we were the
     * presumed-dead owner), storing 0 here would unlock the thief */
    int32_t tag = fc_self_tag();
    __atomic_compare_exchange_n(&s->lock, &tag, 0, 0, __ATOMIC_RELEASE,
                                __ATOMIC_RELAXED);
}

static inline uint64_t fc_hash(const char *key, int32_t len) {
    uint64_t h = 0xcbf29ce484222325ULL;
    for (int32_t i = 0; i < len; i++) {
        h ^= (uint8_t)key[i];
        h *= 0x100000001b3ULL;
    }
    return h;
}

static inline fc_slot *fc_slots(void *base) {
    return (fc_slot *)((char *)base + sizeof(fc_header));
}

int64_t fc_init(void *base, int64_t capacity) {
    /* caller provides zeroed shared memory; capacity must be a power of 2 */
    if (capacity <= 0 || (capacity & (capacity - 1)))
        return -1;
    fc_header *h = (fc_header *)base;
    h->capacity = capacity;
    h->dropped = 0;
    __atomic_store_n(&h->magic, FC_MAGIC, __ATOMIC_RELEASE);
    return 0;
}

int64_t fc_check(void *base) {
    fc_header *h = (fc_header *)base;
    if (__atomic_load_n(&h->magic, __ATOMIC_ACQUIRE) != FC_MAGIC)
        return -1;
    return h->capacity;
}

/* The window transition — mirrors FailedChallengeRateLimitStates.apply
 * (rate_limit.go:125-156 quirks: strict >, exceed resets hits to 0). */
static inline int32_t fc_window(fc_slot *s, int64_t now_ns, int32_t threshold,
                                int32_t match, int32_t *out_hits) {
    int32_t rc = match;
    if (match == FC_OUTSIDE_INTERVAL || match == FC_FIRST_TIME) {
        s->num_hits = 1;
        s->interval_start_ns = now_ns;
    } else {
        s->num_hits += 1;
    }
    if (s->num_hits > threshold) {
        s->num_hits = 0;
        rc |= FC_EXCEEDED_BIT;
        *out_hits = 0;
    } else {
        *out_hits = s->num_hits;
    }
    return rc;
}

int32_t fc_apply(void *base, const char *key, int32_t key_len, int64_t now_ns,
                 int64_t interval_ns, int32_t threshold, int32_t *out_hits) {
    fc_header *hdr = (fc_header *)base;
    fc_slot *slots = fc_slots(base);
    uint64_t mask = (uint64_t)hdr->capacity - 1;
    if (key_len > FC_KEY_MAX)
        key_len = FC_KEY_MAX;
    uint64_t home = fc_hash(key, key_len) & mask;

    int64_t stalest_start = INT64_MAX;
    int64_t stalest_idx = -1;
    for (int32_t p = 0; p < FC_MAX_PROBE; p++) {
        fc_slot *s = &slots[(home + p) & mask];
        fc_lock(s);
        if (s->key_len == 0) {
            memcpy(s->key, key, (size_t)key_len);
            s->key_len = key_len;
            int32_t rc = fc_window(s, now_ns, threshold,
                                   FC_FIRST_TIME, out_hits);
            fc_unlock(s);
            return rc;
        }
        if (s->key_len == key_len && memcmp(s->key, key, (size_t)key_len) == 0) {
            int32_t match = (now_ns - s->interval_start_ns > interval_ns)
                                ? FC_OUTSIDE_INTERVAL
                                : FC_INSIDE_INTERVAL;
            int32_t rc = fc_window(s, now_ns, threshold, match,
                                   out_hits);
            fc_unlock(s);
            return rc;
        }
        if (s->interval_start_ns < stalest_start) {
            stalest_start = s->interval_start_ns;
            stalest_idx = (int64_t)((home + p) & mask);
        }
        fc_unlock(s);
    }

    /* probe window full: steal the stalest slot iff its window expired */
    if (stalest_idx >= 0) {
        fc_slot *s = &slots[stalest_idx];
        fc_lock(s);
        if (s->key_len != 0 && now_ns - s->interval_start_ns > interval_ns) {
            memcpy(s->key, key, (size_t)key_len);
            s->key_len = key_len;
            int32_t rc = fc_window(s, now_ns, threshold,
                                   FC_FIRST_TIME, out_hits);
            fc_unlock(s);
            return rc;
        }
        fc_unlock(s);
    }

    /* degraded: transient unstored first hit */
    __atomic_add_fetch(&hdr->dropped, 1, __ATOMIC_RELAXED);
    int32_t rc = FC_FIRST_TIME | FC_DROPPED_BIT;
    if (1 > threshold) {
        rc |= FC_EXCEEDED_BIT;
        *out_hits = 0;
    } else {
        *out_hits = 1;
    }
    return rc;
}

int64_t fc_count(void *base) {
    fc_header *hdr = (fc_header *)base;
    fc_slot *slots = fc_slots(base);
    int64_t n = 0;
    for (int64_t i = 0; i < hdr->capacity; i++)
        if (slots[i].key_len != 0)
            n++;
    return n;
}

int64_t fc_dropped(void *base) {
    fc_header *hdr = (fc_header *)base;
    return __atomic_load_n(&hdr->dropped, __ATOMIC_RELAXED);
}

/* Copy live entries out for format_states / metrics.  Returns the number
 * of entries written (at most max_entries).  keys_blob must hold
 * max_entries*FC_KEY_MAX bytes; entry i's key is keys_blob[i*FC_KEY_MAX :
 * i*FC_KEY_MAX+key_lens[i]]. */
int64_t fc_snapshot(void *base, char *keys_blob, int32_t *key_lens,
                    int32_t *hits, int64_t *starts, int64_t max_entries) {
    fc_header *hdr = (fc_header *)base;
    fc_slot *slots = fc_slots(base);
    int64_t n = 0;
    for (int64_t i = 0; i < hdr->capacity && n < max_entries; i++) {
        fc_slot *s = &slots[i];
        if (s->key_len == 0)
            continue;
        fc_lock(s);
        if (s->key_len != 0) {
            memcpy(keys_blob + n * FC_KEY_MAX, s->key, (size_t)s->key_len);
            key_lens[n] = s->key_len;
            hits[n] = s->num_hits;
            starts[n] = s->interval_start_ns;
            n++;
        }
        fc_unlock(s);
    }
    return n;
}

/* test hooks: plant/read a raw owner tag so the fault suite can simulate
 * a worker killed while holding a slot lock */
void fc_test_lock_slot(void *base, int64_t idx, int32_t tag) {
    __atomic_store_n(&fc_slots(base)[idx].lock, tag, __ATOMIC_RELEASE);
}

int32_t fc_test_slot_owner(void *base, int64_t idx) {
    return __atomic_load_n(&fc_slots(base)[idx].lock, __ATOMIC_ACQUIRE);
}

/* ------------------------------------------------------------------ *
 * Warm-tier IP window store (mega-state tiering).
 *
 * Holds the full per-rule (num_hits, interval_start) vector of an IP
 * evicted from the device hot tier, so a returning repeat offender
 * refills its window state on slot claim instead of restarting from
 * zero.  One record per IP; the per-rule entries keep their INSERTION
 * order — the hot tier's shadow map is an OrderedDict and a refill
 * round-trip must hand back byte-identical state.
 *
 * Layout: one 128-byte wt_header, then capacity (power of two) 8-byte
 * tags, then capacity 8-byte heads, then an arena of n_blocks 256-byte
 * blocks.  A record is a chain of blocks: its first holds the record
 * header (key, stamp, entry count) and 5 entries, each further one 10
 * entries, so a record takes the room the counters it holds need — 256
 * bytes for an address with two counters, whatever the ruleset's size —
 * and not a stride of 128 + 24 bytes per LOADED rule (24,128 bytes at
 * 1,000 rules, 240,128 at 10,000, a 252 GB mapping at 2^20 positions).
 * Blocks are all alike: one bump pointer and one free list, nothing to
 * fragment.  The arena is sized by the caller (shm.py: what every
 * position needs for a full record, at most 16 blocks a position); a put
 * that finds it exhausted is dropped and counted like one that finds its
 * probe window full.
 *
 * The tag of a position says what its record holds: 0 = empty, 1 =
 * tombstone, anything else = the key's 64-bit hash (0 and 1 moved to 2
 * and 3); its head is the arena index of the record's first block.
 * Open addressing, linear probe bounded at WT_MAX_PROBE, and every probe
 * walks the TAGS: a record's memory is read only where the tag equals
 * the key's, and then its key is compared.  A lookup of an absent key
 * therefore touches a cache line or two of the dense tag array and no
 * record, and no page of the arena exists before a put wrote it.
 *
 * Unlike the fc_* table above, take() deletes — it leaves a tombstone
 * tag (probes continue past it; key search may still early-stop on a
 * genuine empty because inserts never skip one) and gives the record's
 * blocks back.
 *
 * Concurrency: NONE here by design.  The only caller is DeviceWindows,
 * which already serializes every slot/shadow mutation under its own
 * lock — the same external-locking convention as slotmgr.c.
 *
 * Full probe window: steal the stalest record iff its last-touch stamp
 * is older than the expiry horizon (an offender's record is refreshed
 * every spill, so live attackers are never the stalest-and-expired
 * victim); otherwise the new put is dropped and counted — bounded
 * memory, never silent.  Only this path reads stamps, 64 records' worth.
 *
 * The header counts keys looked up (probes), records whose memory a
 * lookup read (record_reads; their quotient is the share of lookups
 * that had to leave the tag array) and the bytes puts wrote into the
 * arena (bytes_written: 128 a record + 24 an entry + 8 a further block).
 */

#define WT_MAGIC 0x626a787774303033LL /* "bjxwt003" — block-chained records */
#define WT_MAX_PROBE 64
#define WT_KEY_MAX 104
#define WT_TAG_EMPTY 0ULL
#define WT_TAG_TOMBSTONE 1ULL
#define WT_BLOCK 256
#define WT_HEAD_ENTRIES 5
#define WT_CONT_ENTRIES 10

typedef struct {
    int64_t magic;
    int64_t capacity;  /* positions; power of two */
    int64_t max_rules; /* most entries a record may hold */
    int64_t count;     /* live records */
    int64_t dropped;   /* puts lost to a full, unexpired probe window or
                          to an exhausted arena */
    int64_t probes;    /* keys looked up by put/take/get/contains_batch */
    int64_t record_reads; /* records whose memory those lookups read */
    int64_t n_blocks;  /* arena size */
    int64_t bump;      /* blocks ever handed out from the arena's end */
    int64_t free_head; /* free list: block index + 1, 0 = empty */
    int64_t free_count;
    int64_t bytes_written;
    int64_t _pad[4];
} wt_header; /* 128 bytes */

typedef struct {
    int32_t rule_id;
    int32_t hits;
    int64_t start_s;
    int64_t start_ns;
} wt_entry; /* 24 bytes */

/* every block begins with its chain link: block index + 1, 0 = last */
typedef struct {
    int64_t next;
    int32_t key_len;
    int32_t n_entries;
    int64_t stamp_ns; /* last-touch; the steal policy's staleness key */
    char key[WT_KEY_MAX];
    wt_entry e[WT_HEAD_ENTRIES];
    int64_t _pad;
} wt_rec; /* one block: the record header and its first entries */

typedef struct {
    int64_t next;
    wt_entry e[WT_CONT_ENTRIES];
    int64_t _pad;
} wt_cont; /* one block: ten further entries */

_Static_assert(sizeof(wt_header) == 128, "wt_header is 128 bytes");
_Static_assert(sizeof(wt_rec) == WT_BLOCK, "wt_rec is one block");
_Static_assert(sizeof(wt_cont) == WT_BLOCK, "wt_cont is one block");

static inline uint64_t *wt_tags(void *base) {
    return (uint64_t *)((char *)base + sizeof(wt_header));
}

static inline int64_t *wt_heads(void *base) {
    return (int64_t *)(wt_tags(base) + ((wt_header *)base)->capacity);
}

static inline char *wt_block(void *base, int64_t b) {
    wt_header *h = (wt_header *)base;
    return (char *)(wt_heads(base) + h->capacity) + b * WT_BLOCK;
}

static inline wt_rec *wt_at(void *base, int64_t i) {
    return (wt_rec *)wt_block(base, wt_heads(base)[i]);
}

static inline int64_t wt_blocks_for(int64_t n) {
    return n <= WT_HEAD_ENTRIES
               ? 1
               : 1 + (n - WT_HEAD_ENTRIES + WT_CONT_ENTRIES - 1) /
                         WT_CONT_ENTRIES;
}

int64_t wt_init(void *base, int64_t capacity, int64_t max_rules,
                int64_t n_blocks) {
    /* caller provides zeroed memory; capacity must be a power of 2 */
    if (capacity <= 0 || (capacity & (capacity - 1)) || max_rules <= 0 ||
        n_blocks <= 0)
        return -1;
    wt_header *h = (wt_header *)base;
    memset(h, 0, sizeof(*h));
    h->capacity = capacity;
    h->max_rules = max_rules;
    h->n_blocks = n_blocks;
    h->magic = WT_MAGIC;
    return 0;
}

int64_t wt_check(void *base) {
    wt_header *h = (wt_header *)base;
    if (h->magic != WT_MAGIC)
        return -1;
    return h->capacity;
}

int64_t wt_max_rules(void *base) { return ((wt_header *)base)->max_rules; }

int64_t wt_len(void *base) { return ((wt_header *)base)->count; }

int64_t wt_dropped(void *base) { return ((wt_header *)base)->dropped; }

int64_t wt_probes(void *base) { return ((wt_header *)base)->probes; }

int64_t wt_record_reads(void *base) {
    return ((wt_header *)base)->record_reads;
}

int64_t wt_bytes_written(void *base) {
    return ((wt_header *)base)->bytes_written;
}

/* blocks of the arena in use by live records */
int64_t wt_blocks_used(void *base) {
    wt_header *h = (wt_header *)base;
    return h->bump - h->free_count;
}

void wt_clear(void *base) {
    /* zeroes the tags and forgets the arena; walks no record */
    wt_header *h = (wt_header *)base;
    memset(wt_tags(base), 0, (size_t)h->capacity * sizeof(uint64_t));
    h->count = 0;
    h->dropped = 0;
    h->bump = 0;
    h->free_head = 0;
    h->free_count = 0;
}

static int64_t wt_alloc(void *base) {
    /* the caller has checked that a block is left */
    wt_header *h = (wt_header *)base;
    if (h->free_head) {
        int64_t b = h->free_head - 1;
        h->free_head = *(int64_t *)wt_block(base, b);
        h->free_count--;
        return b;
    }
    return h->bump++;
}

/* give the chain that starts at block index + 1 `link` back */
static void wt_free_chain(void *base, int64_t link) {
    wt_header *h = (wt_header *)base;
    while (link) {
        int64_t *b = (int64_t *)wt_block(base, link - 1);
        int64_t next = *b;
        *b = h->free_head;
        h->free_head = link;
        h->free_count++;
        link = next;
    }
}

/* Write key's record at position idx.  `first` is the first block of
 * the chain the position already owns (its own or a stolen record's),
 * -1 when it owns none; the chain is reused, lengthened or cut to the
 * blocks n entries need (the caller has checked that they can be had). */
static void wt_fill(void *base, int64_t idx, int64_t first, uint64_t tag,
                    const char *key, int32_t key_len, int64_t now_ns,
                    const int32_t *rule_ids, const int32_t *hits,
                    const int64_t *ss, const int64_t *sns, int64_t n) {
    wt_header *h = (wt_header *)base;
    int64_t old = 0; /* link to the rest of the owned chain */
    if (first < 0)
        first = wt_alloc(base);
    else
        old = ((wt_rec *)wt_block(base, first))->next;
    wt_rec *r = (wt_rec *)wt_block(base, first);
    memcpy(r->key, key, (size_t)key_len);
    r->key_len = key_len;
    r->stamp_ns = now_ns;
    r->n_entries = (int32_t)n;
    h->bytes_written += 128 + n * (int64_t)sizeof(wt_entry);
    int64_t *link = &r->next;
    wt_entry *e = r->e;
    int64_t room = WT_HEAD_ENTRIES;
    for (int64_t k = 0; k < n; k++) {
        if (room == 0) {
            int64_t b = old ? old - 1 : wt_alloc(base);
            wt_cont *c = (wt_cont *)wt_block(base, b);
            if (old)
                old = c->next;
            *link = b + 1;
            link = &c->next;
            e = c->e;
            room = WT_CONT_ENTRIES;
            h->bytes_written += 8;
        }
        e->rule_id = rule_ids[k];
        e->hits = hits[k];
        e->start_s = ss[k];
        e->start_ns = sns[k];
        e++;
        room--;
    }
    *link = 0;
    wt_free_chain(base, old);
    wt_heads(base)[idx] = first;
    wt_tags(base)[idx] = tag;
}

static inline uint64_t wt_tag(uint64_t hash) {
    return hash <= WT_TAG_TOMBSTONE ? hash + 2 : hash;
}

/* The one probe: walk the tags of key's window.  Returns the position
 * of key's record, or -1.  *insert_at (when asked for) is the first
 * tombstone-or-empty position of the window, -1 when all of it is
 * live. */
static int64_t wt_find(void *base, const char *key, int32_t key_len,
                       uint64_t hash, int64_t *insert_at) {
    wt_header *h = (wt_header *)base;
    const uint64_t *tags = wt_tags(base);
    uint64_t mask = (uint64_t)h->capacity - 1;
    uint64_t tag = wt_tag(hash);
    int64_t free_at = -1;
    int64_t found = -1;
    h->probes++;
    for (int32_t p = 0; p < WT_MAX_PROBE; p++) {
        int64_t idx = (int64_t)((hash + p) & mask);
        uint64_t t = tags[idx];
        if (t == tag) {
            wt_rec *r = wt_at(base, idx);
            h->record_reads++;
            if (r->key_len == key_len &&
                memcmp(r->key, key, (size_t)key_len) == 0) {
                found = idx;
                break;
            }
        } else if (t <= WT_TAG_TOMBSTONE) {
            if (free_at < 0)
                free_at = idx;
            if (t == WT_TAG_EMPTY)
                break; /* a key never lives past a genuine empty */
        }
    }
    if (insert_at)
        *insert_at = free_at;
    return found;
}

/* Spill one IP's window vector.  Returns 0 (inserted/updated) or -1
 * (dropped: probe window full of live records younger than expiry, or
 * no block left in the arena). */
int64_t wt_put(void *base, const char *key, int32_t key_len, int64_t now_ns,
               int64_t expiry_ns, const int32_t *rule_ids,
               const int32_t *hits, const int64_t *ss, const int64_t *sns,
               int64_t n) {
    wt_header *h = (wt_header *)base;
    if (key_len > WT_KEY_MAX)
        key_len = WT_KEY_MAX;
    if (n > h->max_rules)
        n = h->max_rules;
    uint64_t hash = fc_hash(key, key_len);
    int64_t insert_at;
    int64_t own = wt_find(base, key, key_len, hash, &insert_at);
    int64_t at = own;
    int64_t first = -1; /* the chain position `at` already owns */
    if (at >= 0) {
        first = wt_heads(base)[at];
    } else if (insert_at >= 0) {
        at = insert_at;
    } else {
        /* all WT_MAX_PROBE positions hold other keys: find the stalest,
         * the first of the window among equals */
        uint64_t mask = (uint64_t)h->capacity - 1;
        int64_t stalest_ns = INT64_MAX;
        for (int32_t p = 0; p < WT_MAX_PROBE; p++) {
            int64_t idx = (int64_t)((hash + p) & mask);
            int64_t stamp = wt_at(base, idx)->stamp_ns;
            if (stamp < stalest_ns) {
                stalest_ns = stamp;
                at = idx;
            }
        }
        h->record_reads += WT_MAX_PROBE;
        h->dropped++;
        /* steal: the victim's windows all expired, so losing its state
         * is semantically a restart-as-first-seen, like fc_apply */
        if (at < 0 || now_ns - stalest_ns <= expiry_ns)
            return -1;
        first = wt_heads(base)[at];
    }
    int64_t have =
        first < 0 ? 0
                  : wt_blocks_for(((wt_rec *)wt_block(base, first))->n_entries);
    if (wt_blocks_for(n) - have > h->free_count + (h->n_blocks - h->bump)) {
        /* the arena is exhausted.  The caller keeps a dropped record's
         * state where it was, so an older copy of it must not stay here */
        if (own >= 0) {
            wt_tags(base)[own] = WT_TAG_TOMBSTONE;
            h->count--;
            wt_free_chain(base, first + 1);
        }
        /* a steal has counted its loss already: it is the put's now,
         * and the victim stays */
        if (own >= 0 || insert_at >= 0)
            h->dropped++;
        return -1;
    }
    if (own < 0 && insert_at >= 0)
        h->count++;
    wt_fill(base, at, first, wt_tag(hash), key, key_len, now_ns, rule_ids,
            hits, ss, sns, n);
    return 0;
}

static int64_t wt_copy_out(void *base, wt_rec *r, int32_t *rule_ids_out,
                           int32_t *hits_out, int64_t *ss_out,
                           int64_t *sns_out) {
    int64_t n = r->n_entries;
    wt_entry *e = r->e;
    int64_t room = WT_HEAD_ENTRIES;
    int64_t link = r->next;
    for (int64_t k = 0; k < n; k++) {
        if (room == 0) {
            wt_cont *c = (wt_cont *)wt_block(base, link - 1);
            link = c->next;
            e = c->e;
            room = WT_CONT_ENTRIES;
        }
        rule_ids_out[k] = e->rule_id;
        hits_out[k] = e->hits;
        ss_out[k] = e->start_s;
        sns_out[k] = e->start_ns;
        e++;
        room--;
    }
    return n;
}

/* Non-deleting read (introspection: DeviceWindows.get / format_states
 * must see warm-spilled state): copy the record's entries out.  Returns
 * the entry count, or -1 when the key is absent. */
int64_t wt_get(void *base, const char *key, int32_t key_len,
               int32_t *rule_ids_out, int32_t *hits_out, int64_t *ss_out,
               int64_t *sns_out) {
    if (key_len > WT_KEY_MAX)
        key_len = WT_KEY_MAX;
    int64_t at = wt_find(base, key, key_len, fc_hash(key, key_len), NULL);
    if (at < 0)
        return -1;
    return wt_copy_out(base, wt_at(base, at), rule_ids_out, hits_out, ss_out,
                       sns_out);
}

/* Move semantics for refill: wt_get, then the record is deleted. */
int64_t wt_take(void *base, const char *key, int32_t key_len,
                int32_t *rule_ids_out, int32_t *hits_out, int64_t *ss_out,
                int64_t *sns_out) {
    wt_header *h = (wt_header *)base;
    if (key_len > WT_KEY_MAX)
        key_len = WT_KEY_MAX;
    int64_t at = wt_find(base, key, key_len, fc_hash(key, key_len), NULL);
    if (at < 0)
        return -1;
    wt_tags(base)[at] = WT_TAG_TOMBSTONE;
    h->count--;
    int64_t n = wt_copy_out(base, wt_at(base, at), rule_ids_out, hits_out,
                            ss_out, sns_out);
    wt_free_chain(base, wt_heads(base)[at] + 1);
    return n;
}

/* Copy live keys out (table order) for introspection.  keys_blob must
 * hold max_entries*WT_KEY_MAX bytes.  Returns the number written. */
int64_t wt_snapshot_keys(void *base, char *keys_blob, int32_t *key_lens,
                         int64_t max_entries) {
    wt_header *h = (wt_header *)base;
    const uint64_t *tags = wt_tags(base);
    int64_t n = 0;
    for (int64_t i = 0; i < h->capacity && n < max_entries; i++) {
        if (tags[i] <= WT_TAG_TOMBSTONE)
            continue;
        wt_rec *r = wt_at(base, i);
        memcpy(keys_blob + n * WT_KEY_MAX, r->key, (size_t)r->key_len);
        key_lens[n] = r->key_len;
        n++;
    }
    return n;
}

/* Batched membership probe over a distinct-ip blob (the admission
 * check's fast path: one C call per batch, not one per IP).  Writes
 * 0/1 per ip into out; returns the number present. */
int64_t wt_contains_batch(void *base, const uint8_t *blob,
                          const int64_t *offs, const int64_t *lens,
                          int64_t n, uint8_t *out) {
    int64_t found = 0;
    for (int64_t i = 0; i < n; i++) {
        const char *key = (const char *)blob + offs[i];
        int32_t key_len = (int32_t)lens[i];
        if (key_len > WT_KEY_MAX)
            key_len = WT_KEY_MAX;
        uint8_t hit =
            wt_find(base, key, key_len, fc_hash(key, key_len), NULL) >= 0;
        out[i] = hit;
        found += hit;
    }
    return found;
}

/* One call a batch for the spills of a placement: wt_put for each of n
 * records, in order.  Record i's key is blob[offs[i] : offs[i] + lens[i]]
 * and its entries are [ent_offs[i], ent_offs[i + 1]) of the four entry
 * arrays.  stored_out[i] = 1 where the put landed, 0 where it was
 * dropped (the caller keeps a dropped record's state where it was).
 * Returns the number stored. */
int64_t wt_put_batch(void *base, const uint8_t *blob, const int64_t *offs,
                     const int64_t *lens, int64_t n, int64_t now_ns,
                     int64_t expiry_ns, const int64_t *ent_offs,
                     const int32_t *rule_ids, const int32_t *hits,
                     const int64_t *ss, const int64_t *sns,
                     uint8_t *stored_out) {
    int64_t stored = 0;
    for (int64_t i = 0; i < n; i++) {
        int64_t e0 = ent_offs[i];
        int64_t rc = wt_put(base, (const char *)blob + offs[i],
                            (int32_t)lens[i], now_ns, expiry_ns,
                            rule_ids + e0, hits + e0, ss + e0, sns + e0,
                            ent_offs[i + 1] - e0);
        stored_out[i] = rc == 0;
        stored += rc == 0;
    }
    return stored;
}

/* One call a batch for the refills of a placement: wt_take for each of n
 * keys, in order.  The records' entries land one record after the other
 * in the four output arrays (which hold n * max_rules entries, the most
 * n records can have); n_out[i] is record i's count, -1 where the key is
 * absent.  Returns the number of records taken. */
int64_t wt_take_batch(void *base, const uint8_t *blob, const int64_t *offs,
                      const int64_t *lens, int64_t n, int32_t *n_out,
                      int32_t *rule_ids_out, int32_t *hits_out,
                      int64_t *ss_out, int64_t *sns_out) {
    int64_t at = 0;
    int64_t taken = 0;
    for (int64_t i = 0; i < n; i++) {
        int64_t got = wt_take(base, (const char *)blob + offs[i],
                              (int32_t)lens[i], rule_ids_out + at,
                              hits_out + at, ss_out + at, sns_out + at);
        n_out[i] = (int32_t)got;
        if (got >= 0) {
            at += got;
            taken++;
        }
    }
    return taken;
}
