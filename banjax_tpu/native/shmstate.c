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
#include <stdlib.h>
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

/* ------------------------------------------------------------------ *
 * The host shadow's native form: a slot-indexed mirror of the device
 * window counters of RESIDENT addresses (matcher/windows.py).
 *
 * The device table holds a counter per (slot, rule); the host keeps what
 * every event wrote there, so that an eviction loses nothing.  For a
 * resident address the slot IS the key: a record per slot, a chain of
 * the warm tier's 256-byte blocks of ten 24-byte entries each (no dense
 * [slots, rules] array: at 10,000 rules that would be the device table
 * again), entries in first-event order.  Four calls move records as
 * arrays, each one C call a batch:
 *
 *   sh_absorb        a chunk's event-final states, upserted by slot
 *   sh_spill         victims' records -> the warm tier, under their keys
 *   sh_refill        returning addresses' records <- the warm tier
 *   sh_restore_rows  the pending slots' counters as _restore_step's rows
 *
 * A record carries a sequence stamp, drawn when it is made (a slot's
 * first event, a refill) and kept when it moves to the caller's dict
 * and back (sh_export / sh_install): format_states sorts by it at the
 * read, and a queued restore names its record by it, so one that went
 * stale (the slot evicted, perhaps re-assigned, since) restores nothing.
 *
 * Process-local memory (malloc), externally locked by DeviceWindows
 * like everything else here.  Block indices, never pointers, are kept
 * across calls: the arena doubles by realloc.
 */

typedef struct {
    int64_t capacity; /* slots */
    int64_t *head;    /* [capacity] first block + 1; 0 = no record */
    int32_t *count;   /* [capacity] entries of the record */
    int64_t *stamp;   /* [capacity] the record's sequence stamp */
    int64_t records;
    int64_t next_stamp;
    wt_cont *blocks;
    int64_t n_blocks;
    int64_t bump;
    int64_t free_head; /* block + 1; 0 = none */
    /* a record in the four-array form wt_put / wt_take speak */
    int32_t *s_rid;
    int32_t *s_hits;
    int64_t *s_ss;
    int64_t *s_sns;
    int64_t s_room;
} sh_t;

void sh_destroy(void *h) {
    sh_t *m = h;
    if (!m)
        return;
    free(m->head);
    free(m->count);
    free(m->stamp);
    free(m->blocks);
    free(m->s_rid);
    free(m->s_hits);
    free(m->s_ss);
    free(m->s_sns);
    free(m);
}

void *sh_create(int64_t capacity) {
    if (capacity < 1)
        return NULL;
    sh_t *m = calloc(1, sizeof(sh_t));
    if (!m)
        return NULL;
    m->capacity = capacity;
    m->n_blocks = capacity < 1024 ? 1024 : capacity;
    m->head = calloc((size_t)capacity, sizeof(int64_t));
    m->count = calloc((size_t)capacity, sizeof(int32_t));
    m->stamp = calloc((size_t)capacity, sizeof(int64_t));
    m->blocks = malloc(sizeof(wt_cont) * (size_t)m->n_blocks);
    if (!m->head || !m->count || !m->stamp || !m->blocks) {
        sh_destroy(m);
        return NULL;
    }
    return m;
}

void sh_clear(void *h) {
    sh_t *m = h;
    memset(m->head, 0, sizeof(int64_t) * (size_t)m->capacity);
    memset(m->count, 0, sizeof(int32_t) * (size_t)m->capacity);
    memset(m->stamp, 0, sizeof(int64_t) * (size_t)m->capacity);
    m->records = 0;
    m->next_stamp = 0;
    m->bump = 0;
    m->free_head = 0;
}

/* 0, or -1 on allocation failure (the mirror stays as it was) */
int64_t sh_grow(void *h, int64_t new_capacity) {
    sh_t *m = h;
    int64_t add = new_capacity - m->capacity;
    if (add <= 0)
        return 0;
    int64_t *hd = realloc(m->head, sizeof(int64_t) * (size_t)new_capacity);
    if (!hd)
        return -1;
    m->head = hd;
    int32_t *ct = realloc(m->count, sizeof(int32_t) * (size_t)new_capacity);
    if (!ct)
        return -1;
    m->count = ct;
    int64_t *st = realloc(m->stamp, sizeof(int64_t) * (size_t)new_capacity);
    if (!st)
        return -1;
    m->stamp = st;
    memset(m->head + m->capacity, 0, sizeof(int64_t) * (size_t)add);
    memset(m->count + m->capacity, 0, sizeof(int32_t) * (size_t)add);
    memset(m->stamp + m->capacity, 0, sizeof(int64_t) * (size_t)add);
    m->capacity = new_capacity;
    return 0;
}

int64_t sh_records(void *h) { return ((sh_t *)h)->records; }

/* a stamp for a record the caller makes in its own dict */
int64_t sh_next_stamp(void *h) { return ++((sh_t *)h)->next_stamp; }

static int64_t sh_alloc(sh_t *m) {
    if (m->free_head) {
        int64_t b = m->free_head - 1;
        m->free_head = m->blocks[b].next;
        return b;
    }
    if (m->bump == m->n_blocks) {
        wt_cont *nb =
            realloc(m->blocks, sizeof(wt_cont) * (size_t)m->n_blocks * 2);
        if (!nb)
            return -1;
        m->blocks = nb;
        m->n_blocks *= 2;
    }
    return m->bump++;
}

static void sh_drop(sh_t *m, int64_t slot) {
    int64_t link = m->head[slot];
    if (!link)
        return;
    while (link) {
        wt_cont *c = &m->blocks[link - 1];
        int64_t next = c->next;
        c->next = m->free_head;
        m->free_head = link;
        link = next;
    }
    m->head[slot] = 0;
    m->count[slot] = 0;
    m->stamp[slot] = 0;
    m->records--;
}

/* Set rule's counter in slot's record; one new to the record goes to
 * its end, and the first of a slot makes the record (the caller stamps
 * it).  0, or -1 when no block could be had. */
static int sh_upsert(sh_t *m, int64_t slot, int32_t rule, int32_t hits,
                     int64_t ss, int64_t sns) {
    int64_t n = m->count[slot];
    int64_t left = n;
    int64_t last = -1;
    wt_entry *e = NULL;
    for (int64_t link = m->head[slot]; link && !e;) {
        wt_cont *c = &m->blocks[link - 1];
        int64_t here = left < WT_CONT_ENTRIES ? left : WT_CONT_ENTRIES;
        for (int64_t k = 0; k < here; k++)
            if (c->e[k].rule_id == rule) {
                e = &c->e[k];
                break;
            }
        left -= here;
        last = link - 1;
        link = c->next;
    }
    if (!e) {
        int64_t pos = n % WT_CONT_ENTRIES;
        if (pos == 0) { /* no record yet, or its last block is full */
            int64_t b = sh_alloc(m);
            if (b < 0)
                return -1;
            m->blocks[b].next = 0;
            if (last < 0) {
                m->head[slot] = b + 1;
                m->records++;
            } else {
                m->blocks[last].next = b + 1;
            }
            last = b;
        }
        e = &m->blocks[last].e[pos];
        e->rule_id = rule;
        m->count[slot] = (int32_t)(n + 1);
    }
    e->hits = hits;
    e->start_s = ss;
    e->start_ns = sns;
    return 0;
}

/* Fold one applied chunk's per-event final counter states in: event k
 * belongs to slot_of_line[line[k]], and the events come in the
 * reference's processing order ((line, rule) ascending), so a (slot,
 * rule)'s last write is its segment-final state and new counters join
 * their record in first-event order.  last_used (the slot table's
 * recency array; 0 = the slot never had an owner since the last clear)
 * drops an event whose slot has none — unreachable while the chunk is
 * pinned.  Returns the events taken in, -1 when no block could be had. */
int64_t sh_absorb(void *h, const int32_t *slot_of_line, int64_t n_lines,
                  const int64_t *last_used, const int32_t *line,
                  const int32_t *rule, const int32_t *hits,
                  const int32_t *ss, const int32_t *sns, int64_t n) {
    sh_t *m = h;
    int64_t taken = 0;
    for (int64_t k = 0; k < n; k++) {
        if (line[k] < 0 || line[k] >= n_lines)
            continue;
        int64_t slot = slot_of_line[line[k]];
        if (slot < 0 || slot >= m->capacity || last_used[slot] == 0)
            continue;
        if (sh_upsert(m, slot, rule[k], hits[k], ss[k], sns[k]) != 0)
            return -1;
        if (!m->stamp[slot]) /* the slot's first event made the record */
            m->stamp[slot] = ++m->next_stamp;
        taken++;
    }
    return taken;
}

static int sh_scratch(sh_t *m, int64_t n) {
    if (n <= m->s_room)
        return 0;
    int64_t room = m->s_room ? m->s_room : 16;
    while (room < n)
        room *= 2;
    int32_t *a = realloc(m->s_rid, sizeof(int32_t) * (size_t)room);
    if (a)
        m->s_rid = a;
    int32_t *b = realloc(m->s_hits, sizeof(int32_t) * (size_t)room);
    if (b)
        m->s_hits = b;
    int64_t *c = realloc(m->s_ss, sizeof(int64_t) * (size_t)room);
    if (c)
        m->s_ss = c;
    int64_t *d = realloc(m->s_sns, sizeof(int64_t) * (size_t)room);
    if (d)
        m->s_sns = d;
    if (!a || !b || !c || !d)
        return -1;
    m->s_room = room;
    return 0;
}

/* slot's entries, in record order, into four arrays; returns their count */
static int64_t sh_copy_out(sh_t *m, int64_t slot, int32_t *rid,
                           int32_t *hits, int64_t *ss, int64_t *sns) {
    int64_t n = m->count[slot];
    int64_t link = m->head[slot];
    for (int64_t k = 0; k < n; link = m->blocks[link - 1].next) {
        wt_cont *c = &m->blocks[link - 1];
        for (int64_t j = 0; j < WT_CONT_ENTRIES && k < n; j++, k++) {
            rid[k] = c->e[j].rule_id;
            hits[k] = c->e[j].hits;
            ss[k] = c->e[j].start_s;
            sns[k] = c->e[j].start_ns;
        }
    }
    return n;
}

/* Make slot's record from n entries (whatever it held goes) under
 * `stamp`, 0 = draw a new one.  Returns the stamp, 0 for an empty
 * record (none is made), -1 when no block could be had. */
int64_t sh_install(void *h, int64_t slot, int64_t stamp, const int32_t *rid,
                   const int32_t *hits, const int64_t *ss,
                   const int64_t *sns, int64_t n) {
    sh_t *m = h;
    if (slot < 0 || slot >= m->capacity)
        return -1;
    sh_drop(m, slot);
    for (int64_t k = 0; k < n; k++)
        if (sh_upsert(m, slot, rid[k], hits[k], ss[k], sns[k]) != 0) {
            sh_drop(m, slot);
            return -1;
        }
    if (!n)
        return 0;
    m->stamp[slot] = stamp ? stamp : ++m->next_stamp;
    return m->stamp[slot];
}

/* The victims of one placement, in eviction order: each record goes to
 * the warm tier under its key (keys + k * stride, key_lens[k]) and out
 * of the mirror.  status_out[k]: 0 = the slot held no record, 1 = the
 * put landed, 2 = it was dropped (or wt_base is NULL: no tier of this
 * kind) and the record is still here — the caller gives it a home
 * (sh_export with drop).  Returns the number landed. */
int64_t sh_spill(void *h, void *wt_base, const int64_t *slots, int64_t n,
                 const uint8_t *keys, const int32_t *key_lens,
                 int64_t stride, int64_t now_ns, int64_t expiry_ns,
                 uint8_t *status_out) {
    sh_t *m = h;
    int64_t landed = 0;
    for (int64_t k = 0; k < n; k++) {
        int64_t slot = slots[k];
        int64_t cnt = m->count[slot];
        if (!cnt) {
            status_out[k] = 0;
            continue;
        }
        status_out[k] = 2;
        if (!wt_base || sh_scratch(m, cnt) != 0)
            continue;
        sh_copy_out(m, slot, m->s_rid, m->s_hits, m->s_ss, m->s_sns);
        if (wt_put(wt_base, (const char *)keys + k * stride, key_lens[k],
                   now_ns, expiry_ns, m->s_rid, m->s_hits, m->s_ss, m->s_sns,
                   cnt) == 0) {
            sh_drop(m, slot);
            status_out[k] = 1;
            landed++;
        }
    }
    return landed;
}

/* The returning addresses of one placement, in placement order: key
 * k's record (blob[offs[k] : offs[k] + lens[k]]) is taken out of the
 * warm tier and made slots[k]'s, under a new stamp.  stamp_out[k] = that
 * stamp, 0 where the tier had no record of the key.  Returns the number
 * found. */
int64_t sh_refill(void *h, void *wt_base, const int32_t *slots,
                  const uint8_t *blob, const int64_t *offs,
                  const int64_t *lens, int64_t n, int64_t *stamp_out) {
    sh_t *m = h;
    int64_t found = 0;
    if (sh_scratch(m, ((wt_header *)wt_base)->max_rules) != 0)
        n = 0;
    for (int64_t k = 0; k < n; k++) {
        int64_t got =
            wt_take(wt_base, (const char *)blob + offs[k], (int32_t)lens[k],
                    m->s_rid, m->s_hits, m->s_ss, m->s_sns);
        int64_t stamp = got < 0 ? 0
                                : sh_install(m, slots[k], 0, m->s_rid,
                                             m->s_hits, m->s_ss, m->s_sns,
                                             got);
        stamp_out[k] = stamp > 0 ? stamp : 0;
        found += got >= 0;
    }
    return found;
}

/* Every slot that holds a record, ascending; out holds `capacity` */
int64_t sh_live_slots(void *h, int32_t *out) {
    sh_t *m = h;
    int64_t n = 0;
    for (int64_t s = 0; s < m->capacity; s++)
        if (m->count[s])
            out[n++] = (int32_t)s;
    return n;
}

/* counts_out[k], stamps_out[k] of slots[k]'s record (0, 0 = none);
 * returns the entries of all of them together */
int64_t sh_counts(void *h, const int32_t *slots, int64_t n,
                  int32_t *counts_out, int64_t *stamps_out) {
    sh_t *m = h;
    int64_t total = 0;
    for (int64_t k = 0; k < n; k++) {
        counts_out[k] = m->count[slots[k]];
        stamps_out[k] = m->stamp[slots[k]];
        total += counts_out[k];
    }
    return total;
}

/* The records of slots[0..n), one after the other, into four arrays of
 * sh_counts' total; with `drop` they leave the mirror (the caller is
 * their home now). */
void sh_export(void *h, const int32_t *slots, int64_t n, int32_t *rid,
               int32_t *hits, int64_t *ss, int64_t *sns, int32_t drop) {
    sh_t *m = h;
    int64_t at = 0;
    for (int64_t k = 0; k < n; k++) {
        at += sh_copy_out(m, slots[k], rid + at, hits + at, ss + at, sns + at);
        if (drop)
            sh_drop(m, slots[k]);
    }
}

/* A queued restore (slots[k], stamps[k]) is live while the slot still
 * holds the record it was queued for.  The counters of the live ones,
 * all together: what sh_restore_rows will write. */
int64_t sh_restore_count(void *h, const int32_t *slots,
                         const int64_t *stamps, int64_t n) {
    sh_t *m = h;
    int64_t total = 0;
    for (int64_t k = 0; k < n; k++)
        if (m->count[slots[k]] && m->stamp[slots[k]] == stamps[k])
            total += m->count[slots[k]];
    return total;
}

/* _restore_step's operand: counter i of the live queued restores, in
 * queue and record order, is column i % chunk of the [5, chunk] block
 * i / chunk of out — (slot, slot * n_rules + rule, hits, start_s,
 * start_ns), the values of NOW: an absorb that landed after the refill
 * is in them.  The caller has filled out with its pads.  Returns the
 * records restored. */
int64_t sh_restore_rows(void *h, const int32_t *slots, const int64_t *stamps,
                        int64_t n, int64_t n_rules, int64_t chunk,
                        int32_t *out) {
    sh_t *m = h;
    int64_t i = 0;
    int64_t records = 0;
    for (int64_t k = 0; k < n; k++) {
        int64_t slot = slots[k];
        int64_t cnt = m->count[slot];
        if (!cnt || m->stamp[slot] != stamps[k])
            continue;
        records++;
        int64_t link = m->head[slot];
        for (int64_t j = 0; j < cnt; link = m->blocks[link - 1].next) {
            wt_cont *c = &m->blocks[link - 1];
            for (int64_t q = 0; q < WT_CONT_ENTRIES && j < cnt; q++, j++, i++) {
                int32_t *col = out + (i / chunk) * 5 * chunk + i % chunk;
                col[0] = (int32_t)slot;
                col[chunk] = (int32_t)(slot * n_rules + c->e[q].rule_id);
                col[2 * chunk] = c->e[q].hits;
                col[3 * chunk] = (int32_t)c->e[q].start_s;
                col[4 * chunk] = (int32_t)c->e[q].start_ns;
            }
        }
    }
    return records;
}
