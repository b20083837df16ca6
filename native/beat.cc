// The native beat (nebula_tpu/common/hostclock.py, docs/observability.md
// "The device timeline"): one pthread that sleeps a fixed period on
// CLOCK_MONOTONIC and keeps count, sum and maximum of its own lateness
// (woke - due).  It touches neither the interpreter nor the device, so
// a beat that is late here was late for want of a core (or because the
// whole guest stood still), never for want of the interpreter lock:
// the Python beat's lateness over this one's is what that lock costs a
// thread that wants to run.
//
// One beat a process.  neb_beat_read hands out the count and the sum
// since the start and the maximum since the previous read, so whoever
// reads once a second gets that second's worst beat.
#include <fcntl.h>
#include <pthread.h>
#include <time.h>
#include <unistd.h>

#include <atomic>
#include <cstdint>

namespace {

pthread_mutex_t g_mu = PTHREAD_MUTEX_INITIALIZER;
pthread_t g_thread;
bool g_running = false;
std::atomic<bool> g_stop{false};
int64_t g_period_ns = 0;
int64_t g_n = 0, g_late_sum_ns = 0, g_late_max_ns = 0;   // under g_mu

int64_t now_ns() {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return int64_t(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

void* beat_main(void*) {
    while (!g_stop.load(std::memory_order_relaxed)) {
        // due is one period from where this beat starts to sleep: a
        // stall is charged to the beat it hit and to no later one
        const int64_t due = now_ns() + g_period_ns;
        timespec ts;
        ts.tv_sec = due / 1000000000;
        ts.tv_nsec = due % 1000000000;
        while (clock_nanosleep(CLOCK_MONOTONIC, TIMER_ABSTIME, &ts,
                               nullptr) != 0) {
        }                                   // EINTR: sleep on
        int64_t late = now_ns() - due;
        if (late < 0) late = 0;
        pthread_mutex_lock(&g_mu);
        g_n += 1;
        g_late_sum_ns += late;
        if (late > g_late_max_ns) g_late_max_ns = late;
        pthread_mutex_unlock(&g_mu);
    }
    return nullptr;
}

// one thread's descriptor of its own schedstat file (neb_runq_ns)
struct SchedFd {
    int fd = -2;                // -2: not opened yet, -1: cannot be
    ~SchedFd() {
        if (fd >= 0) close(fd);
    }
};
thread_local SchedFd t_sched;

}  // namespace

extern "C" {

// The second field of the calling thread's own schedstat file: the ns
// it has sat runnable without a core; -1 where the file cannot be
// read.  The thread's descriptor is opened at its first call and closed
// with the thread.  hostclock.stamp() calls this through a handle that
// KEEPS the interpreter lock (ctypes.PyDLL): os.open and os.pread
// release it, and a stamp must not be a place where the pump hands the
// interpreter to a waiting rider and waits to get it back.
int64_t neb_runq_ns() {
    if (t_sched.fd == -2)
        t_sched.fd = open("/proc/thread-self/schedstat",
                          O_RDONLY | O_CLOEXEC);
    if (t_sched.fd < 0) return -1;
    char buf[96];
    const ssize_t n = pread(t_sched.fd, buf, sizeof(buf) - 1, 0);
    if (n <= 0) return -1;
    buf[n] = 0;
    const char* p = buf;
    while (*p >= '0' && *p <= '9') ++p;         // first field: ran
    if (p == buf || *p != ' ') return -1;
    while (*p == ' ') ++p;
    if (*p < '0' || *p > '9') return -1;
    int64_t v = 0;
    while (*p >= '0' && *p <= '9') v = v * 10 + (*p++ - '0');
    return v;
}

// 0: started.  1: one runs already (its period stands).  -1: no thread.
int neb_beat_start(int64_t period_us) {
    if (period_us <= 0) return -1;
    pthread_mutex_lock(&g_mu);
    if (g_running) {
        pthread_mutex_unlock(&g_mu);
        return 1;
    }
    g_period_ns = period_us * 1000;
    g_n = g_late_sum_ns = g_late_max_ns = 0;
    g_stop.store(false);
    const int rc = pthread_create(&g_thread, nullptr, beat_main, nullptr);
    g_running = rc == 0;
    pthread_mutex_unlock(&g_mu);
    return rc == 0 ? 0 : -1;
}

// out[0] beats since the start, out[1] the sum of their lateness in
// ns, out[2] the largest lateness in ns since the previous read.
void neb_beat_read(int64_t* out) {
    pthread_mutex_lock(&g_mu);
    out[0] = g_n;
    out[1] = g_late_sum_ns;
    out[2] = g_late_max_ns;
    g_late_max_ns = 0;
    pthread_mutex_unlock(&g_mu);
}

void neb_beat_stop() {
    pthread_mutex_lock(&g_mu);
    const bool was = g_running;
    g_running = false;
    pthread_mutex_unlock(&g_mu);
    if (!was) return;
    g_stop.store(true);
    pthread_join(g_thread, nullptr);
}

}  // extern "C"
