// Native dataflow-graph engine: dependency counting, priority scheduling,
// work-stealing worker pool, and topological ordering.
//
// This is the C++ core behind the Python runtime's hot paths — the role
// the reference implements in C with its scheduling loop and lfq
// scheduler (/root/reference/parsec/scheduling.c,
// /root/reference/parsec/mca/sched/lfq — studied for behavior, written
// fresh for this runtime):
//   * tasks are integer ids with a priority and a user tag;
//   * edges are (pred, succ) pairs; each completed task decrements its
//     successors' counters, counter 0 => ready;
//   * run(): N native threads execute ready tasks through a C callback
//     (Python bodies enter via a ctypes trampoline that re-acquires the
//     GIL; native bodies run free);
//   * a shared priority pool plus the completing worker keeping its
//     highest-priority released successor for immediate execution (the
//     reference's es->next_task fast path) — dataflow chains run
//     queue-free;
//   * order(): dependency-respecting, priority-greedy linearisation used
//     to lower a whole taskpool into one XLA program quickly.
//
// Streaming insertion (DTD style) is supported: add_task/add_dep may be
// called while run() is live; quiescence is reached when every inserted
// task has executed and the submitter called seal().
//
// ASYNC chores (the reference's PARSEC_HOOK_RETURN_ASYNC, scheduling.c
// :126-153 + device_gpu.c:2510-2730): run_async() bodies return a status —
// 0 means the body completed synchronously (the worker releases successors
// inline, keep-next fast path intact), nonzero means a device manager took
// ownership and completion arrives LATER through pz_task_done(task_id),
// which runs release_deps natively from whatever thread calls it.  The
// run does not quiesce until every async completion has been signalled;
// pz_graph_fail() aborts a run whose completions can no longer arrive.

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <queue>
#include <thread>
#include <tuple>
#include <vector>

namespace {

struct Task {
    int32_t priority = 0;
    int32_t tenant = 0;  // wdrr bin index (pz_graph_task_tenant)
    int64_t user_tag = 0;
    std::atomic<int32_t> missing{0};  // unresolved predecessors
    std::vector<int64_t> succs;
    std::atomic<bool> done{false};
};

// ready-pool entries carry their priority so heap compares never touch
// the (growable) tasks vector — streaming insertion may reallocate it
using Ready = std::pair<int32_t, int64_t>;  // (priority, id); max-heap

// Scheduler policies where scheduling natively matters (the Python
// roster demonstrates API parity; these two differ under contention):
//   LFQ — per-worker bounded heaps with hierarchical steal (reference
//         mca/sched/lfq + sched_local_queues_utils.h:22-36 hbbuffers);
//   GD  — one global priority heap (reference mca/sched/gd).
enum Policy : int32_t { POLICY_LFQ = 0, POLICY_GD = 1 };

// per-worker bounded buffer (hbbuffer role): overflow spills to the
// shared system queue, so local push/pop is O(log cap) on an
// uncontended mutex and the global heap only sees the excess
constexpr size_t kLocalCap = 256;

struct alignas(64) WorkerQ {
    std::mutex mu;
    std::priority_queue<Ready> heap;
};

// ---- pump scheduler ------------------------------------------------------
//
// The ready-queue state behind the zero-interpreter lifecycle
// (pz_graph_pop_batch / pz_graph_done_batch) and the standalone pz_rq_*
// mirror the Python schedulers hand their queue state to.  Three pop
// disciplines, each a faithful port of its Python counterpart so
// determinism tests hold bit-for-bit:
//   * prio  — (priority desc, distance asc, insertion seq asc), the spq
//             heap key;
//   * wdrr  — weighted deficit round robin over per-tenant bins
//             [Shreedhar & Varghese '96], the serve plane's fairness
//             layer (core/sched/wdrr.py): each visit replenishes
//             quantum x weight credits, a drained bin forfeits its
//             credits and leaves the ring, within-bin order is
//             (priority desc, seq asc);
//   * seeded — deterministic pop-order perturbation for the schedule
//             explorer (sched_rnd_seed): insert at an xorshift64*-drawn
//             position, pop from the back — any ready task may run
//             next, reproducibly per seed.

struct TenantBin {
    int32_t weight = 1;
    int64_t deficit = 0;
    // (priority, -seq, id): max-heap pops (priority desc, seq asc)
    std::priority_queue<std::tuple<int64_t, int64_t, int64_t>> heap;
};

struct SchedQ {
    std::mutex mu;
    int32_t policy = 0;  // 0 = prio, 1 = wdrr
    int32_t quantum = 4;
    int64_t seed = -1;   // >= 0 switches to seeded perturbation
    uint64_t rng = 0;
    int64_t seq = 0;
    int64_t count = 0;
    // prio mode: (priority, -distance, -seq, id)
    std::priority_queue<std::tuple<int64_t, int64_t, int64_t, int64_t>> heap;
    std::vector<int64_t> vec;  // seeded mode
    std::vector<TenantBin> tenants;
    std::vector<int32_t> ring;  // wdrr: bins with queued tasks
    size_t cur = 0;

    uint64_t next_rng() {  // xorshift64*
        uint64_t x = rng;
        x ^= x >> 12;
        x ^= x << 25;
        x ^= x >> 27;
        rng = x;
        return x * 0x2545F4914F6CDD1DULL;
    }

    TenantBin& bin(int32_t t) {
        if (t < 0) t = 0;
        if (static_cast<size_t>(t) >= tenants.size()) tenants.resize(t + 1);
        return tenants[t];
    }

    // caller holds mu
    void push(int64_t prio, int64_t distance, int32_t tenant, int64_t id) {
        ++count;
        int64_t s = seq++;
        if (seed >= 0) {
            size_t pos = vec.empty()
                             ? 0
                             : static_cast<size_t>(next_rng() % (vec.size() + 1));
            vec.insert(vec.begin() + pos, id);
            return;
        }
        if (policy == 1) {
            if (tenant < 0) tenant = 0;
            TenantBin& b = bin(tenant);
            if (b.heap.empty()) ring.push_back(tenant);
            b.heap.push({prio, -s, id});
            return;
        }
        heap.push({prio, -distance, -s, id});
    }

    // caller holds mu; -1 when empty
    int64_t pop() {
        if (seed >= 0) {
            if (vec.empty()) return -1;
            int64_t id = vec.back();
            vec.pop_back();
            --count;
            return id;
        }
        if (policy == 1) {
            while (!ring.empty()) {
                if (cur >= ring.size()) cur = 0;
                TenantBin& b = tenants[ring[cur]];
                if (b.heap.empty()) {
                    // drained since its last pop: retire the bin and
                    // forfeit its credits (mirror of wdrr.py select)
                    b.deficit = 0;
                    ring.erase(ring.begin() + cur);
                    continue;
                }
                if (b.deficit <= 0)
                    b.deficit += static_cast<int64_t>(quantum) * b.weight;
                int64_t id = std::get<2>(b.heap.top());
                b.heap.pop();
                b.deficit -= 1;
                --count;
                if (b.deficit <= 0 || b.heap.empty()) {
                    if (b.heap.empty()) {
                        b.deficit = 0;
                        ring.erase(ring.begin() + cur);
                    } else {
                        ++cur;
                    }
                }
                return id;
            }
            return -1;
        }
        if (heap.empty()) return -1;
        int64_t id = std::get<3>(heap.top());
        heap.pop();
        --count;
        return id;
    }

    void clear() {
        heap = {};
        vec.clear();
        for (TenantBin& b : tenants) {
            b.deficit = 0;
            b.heap = {};
        }
        ring.clear();
        cur = 0;
        count = 0;
    }
};

// lifecycle event published to the observability drain
// (pz_graph_events_drain): kind 0 = dep decrement (a=succ, b=ready),
// kind 1 = ready push (a=task, b=priority), kind 2 = retire
// (a=task, b=accepted)
struct Evt {
    int32_t kind;
    int64_t a;
    int64_t b;
};

enum EvtKind : int32_t { EVT_DEP_DEC = 0, EVT_PUBLISH = 1, EVT_RETIRE = 2 };

struct Graph {
    std::vector<Task*> tasks;
    std::mutex graph_mu;  // guards tasks vector growth + edge insertion
    std::priority_queue<Ready> ready;  // shared system queue
    std::mutex ready_mu;
    std::condition_variable ready_cv;
    std::vector<WorkerQ> wqs;  // sized by run(); empty => global-only
    std::atomic<int32_t> policy{POLICY_LFQ};
    //: bumped on EVERY push (local or global): the idle-wait predicate
    //: compares it against the epoch seen before the pop miss, closing
    //: the lost-wakeup window between pop_ready and wait_for
    std::atomic<uint64_t> push_epoch{0};
    //: per-worker VP (locality domain) ids, set via pz_graph_set_vpmap:
    //: steal walks the SAME-VP ring first, then crosses domains — the
    //: reference lfq's multi-level hbbuffer hierarchy
    //: (sched_local_queues_utils.h:22-36), collapsed to its two
    //: meaningful levels (VP-local, global)
    std::vector<int32_t> vp_of;
    std::atomic<int64_t> n_steals{0};
    std::atomic<int64_t> n_steals_remote{0};  // cross-VP subset
    std::atomic<int64_t> n_executed{0};
    std::atomic<int64_t> n_inserted{0};
    //: signals the double-complete guard REFUSED (a second pz_task_done
    //: for one task): 0 on a healthy run; the hb-check/TSan harnesses
    //: read it to prove the guard actually fired under a seeded race
    std::atomic<int64_t> n_double_completes{0};
    std::atomic<bool> sealed{false};
    std::atomic<bool> failed{false};
    //: pump mode (pz_graph_sched_config): ready pushes route into ``sq``
    //: instead of the worker/global heaps, pops come from
    //: pz_graph_pop_batch (or pop_ready, for worker runs that want the
    //: wdrr/seeded disciplines), and complete() pushes every released
    //: successor instead of keeping one (strict queue ordering)
    std::atomic<bool> pump_on{false};
    SchedQ sq;
    //: lifecycle event buffer for the observability drain — recorded
    //: only while ev_on (the Python side enables it exactly when PINS
    //: subscribers exist), drained in batches by the control plane
    std::atomic<bool> ev_on{false};
    std::mutex ev_mu;
    std::vector<Evt> events;

    ~Graph() {
        for (Task* t : tasks) delete t;
    }
};

void record_evt(Graph* g, int32_t kind, int64_t a, int64_t b) {
    if (!g->ev_on.load(std::memory_order_relaxed)) return;
    std::lock_guard<std::mutex> lk(g->ev_mu);
    g->events.push_back({kind, a, b});
}

using BodyFn = void (*)(int64_t task_id, int64_t user_tag, void* ctx);
// async-capable body: returns 0 (done, complete inline) or nonzero
// (ASYNC — a device manager owns completion, signalled via pz_task_done)
using AsyncBodyFn = int32_t (*)(int64_t task_id, int64_t user_tag, void* ctx);

// adapter so the legacy void-body entry reuses the async worker loop
struct SyncBodyAdapter {
    BodyFn body;
    void* ctx;
};

int32_t sync_body_thunk(int64_t id, int64_t tag, void* ctx) {
    SyncBodyAdapter* a = static_cast<SyncBodyAdapter*>(ctx);
    a->body(id, tag, a->ctx);
    return 0;
}

void push_global(Graph* g, int32_t prio, int64_t id) {
    {
        std::lock_guard<std::mutex> lk(g->ready_mu);
        g->ready.push({prio, id});
    }
    g->push_epoch.fetch_add(1, std::memory_order_release);
    g->ready_cv.notify_one();
}

// pump-mode push: into the SchedQ disciplines, with a publish event for
// the observability drain
void push_pump(Graph* g, int32_t prio, int32_t tenant, int64_t id) {
    {
        std::lock_guard<std::mutex> lk(g->sq.mu);
        g->sq.push(prio, 0, tenant, id);
    }
    record_evt(g, EVT_PUBLISH, id, prio);
    g->push_epoch.fetch_add(1, std::memory_order_release);
    g->ready_cv.notify_one();
}

// wid < 0: caller is not a worker (streaming inserter) — always global.
void push_ready(Graph* g, int32_t prio, int32_t tenant, int64_t id,
                int32_t wid) {
    if (g->pump_on.load(std::memory_order_acquire)) {
        push_pump(g, prio, tenant, id);
        return;
    }
    if (wid >= 0 && g->policy.load(std::memory_order_relaxed) == POLICY_LFQ &&
        static_cast<size_t>(wid) < g->wqs.size()) {
        WorkerQ& q = g->wqs[wid];
        {
            std::lock_guard<std::mutex> lk(q.mu);
            if (q.heap.size() < kLocalCap) {
                q.heap.push({prio, id});
                g->push_epoch.fetch_add(1, std::memory_order_release);
                g->ready_cv.notify_one();  // sleepers may steal it
                return;
            }
        }
    }
    push_global(g, prio, id);
}

// Own queue first, then the shared queue, then steal round-robin from
// the other workers (hierarchical order: nearest neighbour outward —
// the reference walks its NUMA hierarchy; the ring is the 1-level form).
int64_t pop_ready(Graph* g, int32_t wid) {
    if (g->pump_on.load(std::memory_order_acquire)) {
        std::lock_guard<std::mutex> lk(g->sq.mu);
        return g->sq.pop();
    }
    if (wid >= 0 && static_cast<size_t>(wid) < g->wqs.size()) {
        WorkerQ& q = g->wqs[wid];
        std::lock_guard<std::mutex> lk(q.mu);
        if (!q.heap.empty()) {
            int64_t id = q.heap.top().second;
            q.heap.pop();
            return id;
        }
    }
    {
        std::lock_guard<std::mutex> lk(g->ready_mu);
        if (!g->ready.empty()) {
            int64_t id = g->ready.top().second;
            g->ready.pop();
            return id;
        }
    }
    size_t nw = g->wqs.size();
    if (wid >= 0 && nw > 1) {
        // hierarchical steal: pass 0 visits only same-VP victims (the
        // reference walks its NUMA hierarchy bottom-up), pass 1 crosses
        // domains; without a vpmap the single pass is the flat ring
        const bool have_vp = g->vp_of.size() == nw;
        const int32_t myvp = have_vp ? g->vp_of[wid] : 0;
        const int passes = have_vp ? 2 : 1;
        for (int pass = 0; pass < passes; ++pass) {
            for (size_t d = 1; d < nw; ++d) {
                size_t vi = (static_cast<size_t>(wid) + d) % nw;
                if (have_vp && ((g->vp_of[vi] == myvp) != (pass == 0)))
                    continue;
                WorkerQ& v = g->wqs[vi];
                std::unique_lock<std::mutex> lk(v.mu, std::try_to_lock);
                if (!lk.owns_lock() || v.heap.empty()) continue;
                int64_t id = v.heap.top().second;
                v.heap.pop();
                g->n_steals.fetch_add(1, std::memory_order_relaxed);
                if (pass == 1)
                    g->n_steals_remote.fetch_add(1, std::memory_order_relaxed);
                return id;
            }
        }
    }
    return -1;
}

// Complete a task: release successors whose last predecessor this was.
// Returns the highest-priority newly-ready successor for the calling
// worker to run next (the reference keeps it in es->next_task instead of
// round-tripping through the scheduler), or -1.
int64_t complete(Graph* g, int64_t id, int32_t wid) {
    std::vector<int64_t> succs;
    std::vector<Task*> stasks;
    {
        std::lock_guard<std::mutex> lk(g->graph_mu);
        Task* t = g->tasks[id];
        t->done.store(true, std::memory_order_release);
        succs = t->succs;  // snapshot: edges to a done task are rejected
        stasks.reserve(succs.size());
        for (int64_t s : succs) stasks.push_back(g->tasks[s]);
    }
    // pump mode pushes EVERY released successor (strict queue ordering —
    // a kept task would bypass the wdrr/seeded disciplines); worker mode
    // keeps the best one for the es->next_task fast path
    const bool keep_next = !g->pump_on.load(std::memory_order_acquire);
    const bool ev = g->ev_on.load(std::memory_order_relaxed);
    int64_t keep = -1;
    int32_t keep_prio = 0;
    int32_t keep_tenant = 0;
    for (size_t i = 0; i < succs.size(); ++i) {
        Task* st = stasks[i];
        int64_t s = succs[i];
        bool ready = st->missing.fetch_sub(1, std::memory_order_acq_rel) == 1;
        if (ev) record_evt(g, EVT_DEP_DEC, s, ready ? 1 : 0);
        if (ready) {
            if (!keep_next) {
                push_ready(g, st->priority, st->tenant, s, wid);
            } else if (keep < 0) {
                keep = s;
                keep_prio = st->priority;
                keep_tenant = st->tenant;
            } else if (st->priority > keep_prio) {
                push_ready(g, keep_prio, keep_tenant, keep, wid);
                keep = s;
                keep_prio = st->priority;
                keep_tenant = st->tenant;
            } else {
                push_ready(g, st->priority, st->tenant, s, wid);
            }
        }
    }
    g->n_executed.fetch_add(1, std::memory_order_acq_rel);
    return keep;
}

bool all_done(Graph* g) {
    return g->sealed.load(std::memory_order_acquire) &&
           g->n_executed.load(std::memory_order_acquire) ==
               g->n_inserted.load(std::memory_order_acquire);
}

void worker_main(Graph* g, AsyncBodyFn body, void* ctx, int32_t wid) {
    int64_t next = -1;  // kept successor from the previous completion
    for (;;) {
        int64_t id = next;
        next = -1;
        if (id < 0) {
            uint64_t seen = g->push_epoch.load(std::memory_order_acquire);
            id = pop_ready(g, wid);
            if (id < 0) {
                if (all_done(g) || g->failed.load(std::memory_order_acquire))
                    return;
                std::unique_lock<std::mutex> lk(g->ready_mu);
                // predicate re-arms on ANY push since the pop miss (epoch
                // moved), on termination, and on failure — a notify that
                // fired before we were waiting cannot be lost
                g->ready_cv.wait_for(lk, std::chrono::milliseconds(50), [&] {
                    return g->push_epoch.load(std::memory_order_acquire) != seen ||
                           all_done(g) || g->failed.load(std::memory_order_acquire);
                });
                continue;
            }
        }
        Task* t;
        {
            std::lock_guard<std::mutex> lk(g->graph_mu);
            t = g->tasks[id];
        }
        if (body(id, t->user_tag, ctx) != 0) {
            // ASYNC: a device manager owns this task now; its completion
            // (and successor release) arrives through pz_task_done — the
            // worker just moves to the next ready task
            continue;
        }
        next = complete(g, id, wid);
        if (all_done(g)) g->ready_cv.notify_all();
    }
}

void noop_body(int64_t, int64_t, void*) {}

}  // namespace

extern "C" {

void* pz_graph_new(void) { return new Graph(); }

// Destroy synchronizes with stragglers whose last action was releasing
// one of the graph's locks (a drain thread finishing its final
// pz_graph_events_drain, a pump thread's last done_batch): acquiring
// each mutex once here orders those unlocks before the frees in
// ~Graph.  Callers still must not issue NEW pz_graph_* calls
// concurrently with destroy.
void pz_graph_destroy(void* gp) {
    Graph* g = static_cast<Graph*>(gp);
    { std::lock_guard<std::mutex> lk(g->graph_mu); }
    { std::lock_guard<std::mutex> lk(g->ready_mu); }
    { std::lock_guard<std::mutex> lk(g->sq.mu); }
    { std::lock_guard<std::mutex> lk(g->ev_mu); }
    for (WorkerQ& w : g->wqs) { std::lock_guard<std::mutex> lk(w.mu); }
    delete g;
}

// Add a task; returns its id. May be called while run() is live
// (streaming/DTD insertion). Declare predecessors with pz_graph_add_dep,
// then pz_graph_task_commit to arm the task.
int64_t pz_graph_add_task(void* gp, int32_t priority, int64_t user_tag) {
    Graph* g = static_cast<Graph*>(gp);
    Task* t = new Task();
    t->priority = priority;
    t->user_tag = user_tag;
    t->missing.store(1, std::memory_order_relaxed);  // commit token
    std::lock_guard<std::mutex> lk(g->graph_mu);
    g->tasks.push_back(t);
    g->n_inserted.fetch_add(1, std::memory_order_acq_rel);
    return static_cast<int64_t>(g->tasks.size()) - 1;
}

// Declare succ depends on pred. Returns 1 if the edge was recorded, 0 if
// pred already completed (the dependency is already satisfied), -1 on a
// bad id.
int pz_graph_add_dep(void* gp, int64_t pred, int64_t succ) {
    Graph* g = static_cast<Graph*>(gp);
    std::lock_guard<std::mutex> lk(g->graph_mu);
    if (pred < 0 || succ < 0 ||
        pred >= static_cast<int64_t>(g->tasks.size()) ||
        succ >= static_cast<int64_t>(g->tasks.size()))
        return -1;
    Task* pt = g->tasks[pred];
    if (pt->done.load(std::memory_order_acquire)) return 0;
    g->tasks[succ]->missing.fetch_add(1, std::memory_order_acq_rel);
    pt->succs.push_back(succ);
    return 1;
}

// All predecessors declared: drop the commit token; the task becomes
// ready when its counter reaches zero.
void pz_graph_task_commit(void* gp, int64_t id) {
    Graph* g = static_cast<Graph*>(gp);
    Task* t;
    {
        std::lock_guard<std::mutex> lk(g->graph_mu);
        t = g->tasks[id];
    }
    if (t->missing.fetch_sub(1, std::memory_order_acq_rel) == 1)
        push_ready(g, t->priority, t->tenant, id, -1);  // inserter: global
}

// Bulk declaration (the attach plan's bind, dsl/native_exec.py): ``n``
// tasks with priorities ``prio[i]``, user tags ``i`` and one tenant,
// then ``nedges`` edges whose ids count from the first of these tasks;
// returns that first id.  The same state n pz_graph_add_task and nedges
// pz_graph_add_dep calls leave, in one crossing of the ctypes boundary.
// Nothing is committed: pz_graph_commit_range arms the tasks once the
// ready queue is configured.  -1 on an edge that leaves [0, n).
int64_t pz_graph_add_bulk(void* gp, int64_t n, const int32_t* prio,
                          int32_t tenant, int64_t nedges,
                          const int64_t* pred, const int64_t* succ) {
    Graph* g = static_cast<Graph*>(gp);
    for (int64_t e = 0; e < nedges; ++e)
        if (pred[e] < 0 || succ[e] < 0 || pred[e] >= n || succ[e] >= n)
            return -1;
    std::lock_guard<std::mutex> lk(g->graph_mu);
    const int64_t base = static_cast<int64_t>(g->tasks.size());
    g->tasks.reserve(g->tasks.size() + static_cast<size_t>(n));
    for (int64_t i = 0; i < n; ++i) {
        Task* t = new Task();
        t->priority = prio[i];
        t->user_tag = i;
        t->tenant = tenant < 0 ? 0 : tenant;
        t->missing.store(1, std::memory_order_relaxed);  // commit token
        g->tasks.push_back(t);
    }
    g->n_inserted.fetch_add(n, std::memory_order_acq_rel);
    for (int64_t e = 0; e < nedges; ++e) {
        g->tasks[base + succ[e]]->missing.fetch_add(
            1, std::memory_order_acq_rel);
        g->tasks[base + pred[e]]->succs.push_back(base + succ[e]);
    }
    return base;
}

// Commit tasks [first, first + n) in id order (see pz_graph_task_commit).
void pz_graph_commit_range(void* gp, int64_t first, int64_t n) {
    for (int64_t id = first; id < first + n; ++id)
        pz_graph_task_commit(gp, id);
}

// Reset a QUIESCED graph for re-execution over the same structure: every
// task returns to uncommitted (missing = commit token + in-degree), the
// caller then re-commits exactly as after construction (local tasks by
// the owner, phantoms by the network).  Returns -1 if tasks are still
// outstanding.  The reuse path amortizes graph construction across
// repeated same-shape runs — the role the reference's compile-time
// jdf2c-generated structures play.
int pz_graph_reset(void* gp) {
    Graph* g = static_cast<Graph*>(gp);
    std::lock_guard<std::mutex> lk(g->graph_mu);
    if (g->n_executed.load(std::memory_order_acquire) !=
        g->n_inserted.load(std::memory_order_acquire))
        return -1;
    for (Task* t : g->tasks) {
        t->missing.store(1, std::memory_order_relaxed);
        t->done.store(false, std::memory_order_relaxed);
    }
    for (Task* t : g->tasks)
        for (int64_t s : t->succs)
            g->tasks[s]->missing.fetch_add(1, std::memory_order_relaxed);
    {
        std::lock_guard<std::mutex> rk(g->ready_mu);
        while (!g->ready.empty()) g->ready.pop();
    }
    for (auto& q : g->wqs) {
        std::lock_guard<std::mutex> qk(q.mu);
        while (!q.heap.empty()) q.heap.pop();
    }
    {
        std::lock_guard<std::mutex> sk(g->sq.mu);
        g->sq.clear();
    }
    {
        std::lock_guard<std::mutex> ek(g->ev_mu);
        g->events.clear();
    }
    g->n_executed.store(0, std::memory_order_release);
    g->failed.store(false, std::memory_order_relaxed);
    return 0;
}

// Select the scheduling policy (0 = lfq per-worker + steal, 1 = gd
// global heap). Takes effect for pushes from the next run.
void pz_graph_set_policy(void* gp, int32_t policy) {
    static_cast<Graph*>(gp)->policy.store(
        policy == 1 ? POLICY_GD : POLICY_LFQ, std::memory_order_relaxed);
}

int64_t pz_graph_steals(void* gp) {
    return static_cast<Graph*>(gp)->n_steals.load(std::memory_order_relaxed);
}

int64_t pz_graph_steals_remote(void* gp) {
    return static_cast<Graph*>(gp)->n_steals_remote.load(
        std::memory_order_relaxed);
}

// Assign each worker (by id, for the NEXT run) to a VP / locality
// domain: steal prefers same-VP victims (reference vpmap +
// sched_local_queues_utils.h hierarchy).
void pz_graph_set_vpmap(void* gp, const int32_t* vp, int64_t n) {
    Graph* g = static_cast<Graph*>(gp);
    g->vp_of.assign(vp, vp + n);
}

// No more tasks will be inserted; run() returns once everything executed.
void pz_graph_seal(void* gp) {
    Graph* g = static_cast<Graph*>(gp);
    g->sealed.store(true, std::memory_order_release);
    g->ready_cv.notify_all();
}

// Shared run harness over the async-capable worker loop.
int64_t run_workers(Graph* g, AsyncBodyFn body, void* ctx, int32_t nthreads) {
    if (nthreads < 1) nthreads = 1;
    if (g->policy.load(std::memory_order_relaxed) == POLICY_LFQ)
        g->wqs = std::vector<WorkerQ>(nthreads);
    else
        g->wqs.clear();
    std::vector<std::thread> ts;
    ts.reserve(nthreads - 1);
    for (int32_t i = 1; i < nthreads; ++i)
        ts.emplace_back(worker_main, g, body, ctx, i);
    worker_main(g, body, ctx, 0);
    for (auto& th : ts) th.join();
    if (!all_done(g)) return -1;
    return g->n_executed.load(std::memory_order_acquire);
}

// Execute the graph with nthreads native workers. Returns the number of
// executed tasks, or -1 if the graph did not quiesce (cycle or
// uncommitted task detected at seal time).
int64_t pz_graph_run(void* gp, BodyFn body, void* ctx, int32_t nthreads) {
    SyncBodyAdapter a{body, ctx};
    return run_workers(static_cast<Graph*>(gp), sync_body_thunk, &a, nthreads);
}

// Execute with an async-capable body: a nonzero body return means the
// task's completion will be signalled later via pz_task_done (the
// reference's ASYNC hook status — a device manager owns the task).  The
// run blocks until every task, async ones included, has completed.
int64_t pz_graph_run_async(void* gp, AsyncBodyFn body, void* ctx,
                           int32_t nthreads) {
    return run_workers(static_cast<Graph*>(gp), body, ctx, nthreads);
}

// Native completion entry for ASYNC tasks: runs release_deps (successor
// counter decrements + ready-queue pushes) entirely natively, from ANY
// thread (typically the device manager's completion callback — the
// reference's complete_execution reached from the GPU manager,
// device_gpu.c:2510-2730).  Returns 0 on success, -1 on a bad id, -2 if
// the task had already completed (straggler callback after shutdown or a
// double signal) — callers treat -2 as a harmless no-op at teardown.
int pz_task_done(void* gp, int64_t id) {
    Graph* g = static_cast<Graph*>(gp);
    Task* t;
    {
        std::lock_guard<std::mutex> lk(g->graph_mu);
        if (id < 0 || id >= static_cast<int64_t>(g->tasks.size())) return -1;
        t = g->tasks[id];
        // atomic claim: two racing signals for the same task must resolve
        // to exactly one release pass (complete() re-stores done=true,
        // which is idempotent)
        if (t->done.exchange(true, std::memory_order_acq_rel)) {
            g->n_double_completes.fetch_add(1, std::memory_order_relaxed);
            return -2;
        }
    }
    // wid = -1: the caller is not a worker, so newly-ready successors go
    // to the shared queue; the "kept" successor has no worker to run on
    // either — push it globally too
    int64_t keep = complete(g, id, -1);
    if (keep >= 0) {
        int32_t prio, tenant;
        {
            std::lock_guard<std::mutex> lk(g->graph_mu);
            prio = g->tasks[keep]->priority;
            tenant = g->tasks[keep]->tenant;
        }
        push_ready(g, prio, tenant, keep, -1);
    }
    record_evt(g, EVT_RETIRE, id, 1);
    // this may have been the LAST outstanding completion: wake sleepers
    // so the run can quiesce even when no push happened
    g->ready_cv.notify_all();
    return 0;
}

// Abort a live run: completions that can no longer arrive (a failed
// device pool) must not hang the workers forever.  Workers drain their
// current body and exit; pz_graph_run*/run() then reports non-quiescence.
void pz_graph_fail(void* gp) {
    Graph* g = static_cast<Graph*>(gp);
    g->failed.store(true, std::memory_order_release);
    g->ready_cv.notify_all();
}

// Dispatch-bound benchmark entry: run with a native no-op body (no GIL
// round-trip), isolating pure scheduling throughput.
int64_t pz_graph_run_noop(void* gp, int32_t nthreads) {
    return pz_graph_run(gp, noop_body, nullptr, nthreads);
}

int64_t pz_graph_executed(void* gp) {
    return static_cast<Graph*>(gp)->n_executed.load(std::memory_order_acquire);
}

// Refused double-completion signals (the atomic claim in pz_task_done
// rejected a second signal for one task).  0 on a healthy run — the
// runtime race checkers pin this.
int64_t pz_graph_double_completes(void* gp) {
    return static_cast<Graph*>(gp)->n_double_completes.load(
        std::memory_order_relaxed);
}

// Dependency-respecting, priority-greedy linearisation into out[0..n).
// Returns the count written, or -1 if the graph has a cycle / uncommitted
// tasks. Single-threaded; does not consume the graph.
int64_t pz_graph_order(void* gp, int64_t* out, int64_t cap) {
    Graph* g = static_cast<Graph*>(gp);
    std::lock_guard<std::mutex> lk(g->graph_mu);
    int64_t n = static_cast<int64_t>(g->tasks.size());
    if (cap < n) return -1;
    std::vector<int32_t> miss(n);
    for (int64_t i = 0; i < n; ++i)
        miss[i] = g->tasks[i]->missing.load(std::memory_order_relaxed) - 1;
    // ids negated: equal-priority tasks pop in insertion order, matching
    // the Python heap's (−prio, seq) tie-break for deterministic lowering
    std::priority_queue<Ready> pq;
    for (int64_t i = 0; i < n; ++i)
        if (miss[i] == 0) pq.push({g->tasks[i]->priority, -i});
    int64_t written = 0;
    while (!pq.empty()) {
        int64_t id = -pq.top().second;
        pq.pop();
        out[written++] = id;
        for (int64_t s : g->tasks[id]->succs)
            if (--miss[s] == 0) pq.push({g->tasks[s]->priority, -s});
    }
    return written == n ? written : -1;
}

// ---- zero-interpreter lifecycle (pump mode) ------------------------------
//
// The batched hot loop behind NativeExecutor's pump: the control plane
// makes ONE call per batch in each direction (pop_batch out, done_batch
// in) and the entire per-task lifecycle — dep-counter decrement,
// ready-queue push/pop under the configured discipline, retire counting,
// quiescence — runs in here without entering the interpreter.

// Route ready pushes/pops through the SchedQ disciplines.  policy: 0 =
// (priority, insertion) heap, 1 = wdrr per-tenant deficit round robin;
// quantum: wdrr credits per visit (scaled by tenant weight; < 1 keeps
// the default 4); seed >= 0: seeded pop-order perturbation for the
// schedule explorer (overrides policy ordering).  Must be called BEFORE
// tasks commit — commit-time pushes land in the configured queues.
void pz_graph_sched_config(void* gp, int32_t policy, int32_t quantum,
                           int64_t seed) {
    Graph* g = static_cast<Graph*>(gp);
    {
        std::lock_guard<std::mutex> lk(g->sq.mu);
        g->sq.policy = policy == 1 ? 1 : 0;
        if (quantum >= 1) g->sq.quantum = quantum;
        g->sq.seed = seed;
        if (seed >= 0)
            g->sq.rng = static_cast<uint64_t>(seed) * 0x9E3779B97F4A7C15ULL +
                        0x2545F4914F6CDD1DULL;
    }
    g->pump_on.store(true, std::memory_order_release);
}

// Assign a task to a wdrr tenant bin (before its commit).
void pz_graph_task_tenant(void* gp, int64_t id, int32_t tenant) {
    Graph* g = static_cast<Graph*>(gp);
    std::lock_guard<std::mutex> lk(g->graph_mu);
    if (id < 0 || id >= static_cast<int64_t>(g->tasks.size())) return;
    g->tasks[id]->tenant = tenant < 0 ? 0 : tenant;
}

// (Re-)tune a tenant bin's wdrr weight — weights are service-managed
// and the latest admitted pool wins, mirroring wdrr.py.
void pz_graph_tenant_weight(void* gp, int32_t tenant, int32_t weight) {
    Graph* g = static_cast<Graph*>(gp);
    std::lock_guard<std::mutex> lk(g->sq.mu);
    g->sq.bin(tenant).weight = weight < 1 ? 1 : weight;
}

// Pop up to cap ready task ids under the configured discipline; returns
// the count written (0 = nothing ready right now).
int64_t pz_graph_pop_batch(void* gp, int64_t* out, int64_t cap) {
    Graph* g = static_cast<Graph*>(gp);
    std::lock_guard<std::mutex> lk(g->sq.mu);
    int64_t n = 0;
    while (n < cap) {
        int64_t id = g->sq.pop();
        if (id < 0) break;
        out[n++] = id;
    }
    return n;
}

// Retire a batch: each task's successors are decremented and newly-ready
// ones pushed — natively, in one call for the whole batch.  Double
// completions are refused per task (counted, skipped).  Returns the
// number accepted.
int64_t pz_graph_done_batch(void* gp, const int64_t* ids, int64_t n) {
    Graph* g = static_cast<Graph*>(gp);
    int64_t accepted = 0;
    for (int64_t i = 0; i < n; ++i) {
        int64_t id = ids[i];
        Task* t;
        {
            std::lock_guard<std::mutex> lk(g->graph_mu);
            if (id < 0 || id >= static_cast<int64_t>(g->tasks.size()))
                continue;
            t = g->tasks[id];
            if (t->done.exchange(true, std::memory_order_acq_rel)) {
                g->n_double_completes.fetch_add(1, std::memory_order_relaxed);
                record_evt(g, EVT_RETIRE, id, 0);
                continue;
            }
        }
        complete(g, id, -1);  // pump routing: every successor is pushed
        record_evt(g, EVT_RETIRE, id, 1);
        ++accepted;
    }
    g->ready_cv.notify_all();
    return accepted;
}

// 1 when every inserted task has retired and the graph is sealed.
int32_t pz_graph_quiesced(void* gp) {
    return all_done(static_cast<Graph*>(gp)) ? 1 : 0;
}

// Queued-task estimate in the pump scheduler (PAPI-SDE style counter).
int64_t pz_graph_sched_pending(void* gp) {
    Graph* g = static_cast<Graph*>(gp);
    std::lock_guard<std::mutex> lk(g->sq.mu);
    return g->sq.count;
}

// Enable/disable lifecycle event recording.  The control plane flips
// this on exactly when PINS subscribers exist — recording is a relaxed
// load on the hot path when off.
void pz_graph_events_enable(void* gp, int32_t on) {
    static_cast<Graph*>(gp)->ev_on.store(on != 0, std::memory_order_relaxed);
}

// Drain up to cap buffered lifecycle events into the parallel arrays
// (kind, a, b) — see EvtKind; returns the count drained.  The Python
// side republishes them through PINS (DEP_DECREMENT / SCHEDULE /
// NATIVE_TASK_DONE) so hb-check, critpath and the binary traces keep
// seeing native-scheduled runs.
int64_t pz_graph_events_drain(void* gp, int32_t* kinds, int64_t* a,
                              int64_t* b, int64_t cap) {
    Graph* g = static_cast<Graph*>(gp);
    std::lock_guard<std::mutex> lk(g->ev_mu);
    int64_t n = static_cast<int64_t>(g->events.size());
    if (n > cap) n = cap;
    for (int64_t i = 0; i < n; ++i) {
        kinds[i] = g->events[i].kind;
        a[i] = g->events[i].a;
        b[i] = g->events[i].b;
    }
    g->events.erase(g->events.begin(), g->events.begin() + n);
    return n;
}

// ---- standalone ready queue (native-mirror for the Python schedulers) ----
//
// The Python spq/wdrr schedulers can hand their queue STATE to this
// object (ownership handoff: the task object stays in a Python dict
// keyed by handle; the pop ORDER is decided here) — one implementation
// of the disciplines shared with the pump above, so worker-based and
// pump-based runs order identically.

void* pz_rq_new(int32_t policy, int32_t quantum, int64_t seed) {
    SchedQ* q = new SchedQ();
    q->policy = policy == 1 ? 1 : 0;
    if (quantum >= 1) q->quantum = quantum;
    q->seed = seed;
    if (seed >= 0)
        q->rng = static_cast<uint64_t>(seed) * 0x9E3779B97F4A7C15ULL +
                 0x2545F4914F6CDD1DULL;
    return q;
}

void pz_rq_destroy(void* qp) { delete static_cast<SchedQ*>(qp); }

void pz_rq_tenant_weight(void* qp, int32_t tenant, int32_t weight) {
    SchedQ* q = static_cast<SchedQ*>(qp);
    std::lock_guard<std::mutex> lk(q->mu);
    q->bin(tenant).weight = weight < 1 ? 1 : weight;
}

void pz_rq_push(void* qp, int64_t priority, int64_t distance, int32_t tenant,
                int64_t handle) {
    SchedQ* q = static_cast<SchedQ*>(qp);
    std::lock_guard<std::mutex> lk(q->mu);
    q->push(priority, distance, tenant, handle);
}

int64_t pz_rq_pop(void* qp) {
    SchedQ* q = static_cast<SchedQ*>(qp);
    std::lock_guard<std::mutex> lk(q->mu);
    return q->pop();
}

int64_t pz_rq_count(void* qp) {
    SchedQ* q = static_cast<SchedQ*>(qp);
    std::lock_guard<std::mutex> lk(q->mu);
    return q->count;
}

void pz_rq_clear(void* qp) {
    SchedQ* q = static_cast<SchedQ*>(qp);
    std::lock_guard<std::mutex> lk(q->mu);
    q->clear();
}

}  // extern "C"
